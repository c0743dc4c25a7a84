"""Share of the calls' wall time outside the fleet program's execution, in
percent: each call's wall time less the program's own timing of it
(``serving_jax.last_run_obs()["exec_s"]``), over the calls' wall time.
What is left is the experiment's host path: the request stream's padding
into the program's shapes, ``summarize`` and the harness."""


def read(d):
    walls = d.get("walls_s")
    if not walls:
        return None
    return 100.0 * (sum(walls) - sum(d["execs_s"])) / sum(walls)
