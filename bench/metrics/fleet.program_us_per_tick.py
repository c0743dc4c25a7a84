"""Microseconds of the fleet program per simulated tick and grid point: the
program's own timing of each call in the window
(``serving_jax.last_run_obs()["exec_s"]``: dispatch to the host copy of
its output, the device bound by the tick scan) over the ticks times the
grid points of the calls."""


def read(d):
    execs = d.get("execs_s")
    if not execs:
        return None
    return sum(execs) / (len(execs) * d["ticks"] * d["points"]) * 1e6
