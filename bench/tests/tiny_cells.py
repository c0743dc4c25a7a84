"""The benchmark's cells cut to CPU test size: the same files, systems and
reference, with the widths (a replica's from its reference's ``TINY``), the
fleet and the traffic made tiny, run without the harness's look for a
chip."""

from __future__ import annotations

import copy
import dataclasses
import pathlib
from types import SimpleNamespace

ROOT = pathlib.Path(__file__).resolve().parents[2]

FAKE_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e9}


def tiny_cell(name: str, root: pathlib.Path = ROOT):
    from bench.lib import harness

    cell = copy.deepcopy(harness.resolve(root, name))
    conf, t = cell["config"], cell["traffic"]
    if conf["system"] == "replica":
        conf["model"].update(harness.load_module(cell["reference"]).TINY)
        conf["engine"].update(max_slots=4, max_len=64)
        conf["limits"].update(max_logit_gap=0.05, min_sampled_tokens=16)
        t["prompt"].update(median=20, min=4, max=40)
        t["output"].update(median=6, min=2, max=20)
        t.update(in_flight=2, sample_tokens=32)
    else:
        from repro.sched.scenarios import get_scenario

        cfg = get_scenario("serve_flash_crowd").serving_config(quick=True)
        conf["fleet"] = dataclasses.asdict(cfg)
        t["trace"].update(n_servers=400, n_short=8, horizon_s=4 * 3600.0)
        # ~1,000 requests: two trajectories' waits lie further apart than
        # at 20,000 (wait_gap up to 0.043 in the point cell and 0.21 in
        # the cube over 6-12 seeds on a CPU)
        t["limits"] = dict(wait_gap=0.15 if t["mode"] == "point" else 0.5,
                           p50_wait_gap=0.5, done_gap=0.05,
                           transients_gap=0.1)
        if t["mode"] == "cube":
            t["grid"]["max_transients"] = [cfg.max_transient // 2,
                                           cfg.max_transient]
    return cell


def run_tiny(name: str, seed: int, seconds: float, *, control=False,
             alter=None, patch=None, root: pathlib.Path = ROOT):
    """One run of a tiny cell on the CPU; returns (result line, output)."""
    import io
    import json
    from contextlib import redirect_stdout

    import jax

    from bench.lib import harness

    cell = tiny_cell(name, root)
    args = SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                           control=int(control), rate=None, workload=name)
    ctx = harness.make_context(root, args, cell, jax.devices()[:1],
                               FAKE_PEAKS)
    system = harness.load_module(cell["system"])
    if patch is not None:
        patch(system)
    out = (system.run(ctx, alter=alter) if alter is not None
           else system.run(ctx))
    buf = io.StringIO()
    with redirect_stdout(buf):
        harness.emit(ctx, cell, out)
    return json.loads(buf.getvalue().strip().splitlines()[-1]), out
