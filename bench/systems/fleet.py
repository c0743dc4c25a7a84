"""Runs the fleet cells: the fleet program (``runtime.serving_jax``) at
paper scale, called again and again through its public entry points,
``run_workload`` (one grid point) or ``sweep_cube`` (a grid as one
program), each call with the next simulation seed.

The program's raw per-request answers are taken where they are produced:
the harness wraps ``serving_jax.get_program`` so that each call's device
output is kept (no copy, no extra work on the timed path), and checks it
once the window has closed: against the guarantees, and against the plain
reference's own simulation of the same policy.

A traced run traces a slice of the window, its first ``trace_seconds``:
a whole call's op-level trace is some 300 MB per grid point (the tick scan
runs thousands of small ops per tick), too much to write and read in a
run. The slice is opened and closed by a thread of its own, since the
calls block the main thread for seconds at a time.
"""

from __future__ import annotations

import gc
import threading
import time
from typing import Dict, List

import numpy as np

from bench.lib import fleet_trace, harness
from bench.lib import trace as tr
from bench.reference import fleet as ref

#: the answers of a call that the checks read
ANSWERS = ("start", "finish", "busy", "cap", "active_transients")


class Recorder:
    """Keeps each program call's output; ``alter`` (tests only) changes an
    answer where it is produced."""

    def __init__(self, sj, alter=None):
        self.sj, self.orig, self.alter = sj, sj.get_program, alter
        self.outs: List[Dict] = []

    def __enter__(self):
        def get_program(spec, *, batch=None):
            fn = self.orig(spec, batch=batch)

            def call(params, consts, key):
                out = fn(params, consts, key)
                if self.alter is not None:
                    out = self.alter(out)
                self.outs.append(out)
                return out
            return call

        self.sj.get_program = get_program
        return self

    def __exit__(self, *exc):
        self.sj.get_program = self.orig


def sim_seeds(seed: int, n: int) -> List[int]:
    return [int(s) for s in np.random.default_rng([seed, 4]).integers(
        0, 2 ** 31 - 1, n)]


def setup(ctx):
    from repro.runtime.serving import Request, ServingFleetConfig

    fleet = ctx.config["fleet"]
    cfg = ServingFleetConfig(**fleet)
    trace = fleet_trace.fleet_trace(ctx.traffic["trace"], fleet)
    arrival, gen = fleet_trace.reorder_sizes(trace, ctx.seed)
    requests = [Request(i, int(a), int(g))
                for i, (a, g) in enumerate(zip(arrival, gen))]
    return cfg, trace, arrival, gen, requests


def make_call(ctx, sj, cfg, requests, trace):
    t = ctx.traffic
    kw = dict(drain_preference=ctx.config["drain_preference"])
    if t["mode"] == "point":
        def call(seed):
            m, _, _ = sj.run_workload(cfg, requests, trace["pinned"],
                                      trace["max_ticks"], sim_seed=seed,
                                      **kw)
            return [m]
        return call, [(cfg.threshold, cfg.max_transient)]
    grid = t["grid"]
    thr, ks = grid["thresholds"], grid["max_transients"]
    points = [(a, b) for a in thr for b in ks]

    def call(seed):
        grids, _ = sj.sweep_cube(cfg, requests, trace["pinned"],
                                 trace["max_ticks"], thresholds=thr,
                                 max_transients=ks,
                                 max_slots_values=[cfg.max_slots],
                                 sim_seeds=(seed,), batch=t["batch"], **kw)
        return [{k: v[i, j, 0] for k, v in grids.items()}
                for i in range(len(thr)) for j in range(len(ks))]
    return call, points


def run(ctx, alter=None) -> Dict:
    import jax

    from repro.runtime import serving_jax as sj

    cfg, trace, arrival, gen, requests = setup(ctx)
    n_req = len(requests)
    call, points = make_call(ctx, sj, cfg, requests, trace)
    n_points = len(points)
    seeds = iter(sim_seeds(ctx.seed, 10_000))
    spans = ctx.spans
    with Recorder(sj, alter) as rec:
        call(next(seeds))  # warm: compiles, or loads from the cache
        rec.outs.clear()
        slicer = None
        if ctx.trace:
            ctx.tracer.start()
            slicer = threading.Thread(target=trace_slice, args=(
                jax, ctx.tracer, float(ctx.traffic["trace_seconds"])))
        miss0 = sj.cache_info().misses
        spans.reset()
        reported, walls, execs = [], [], []
        ctx.compiles.active = True
        setup_s = time.time() - ctx.t_start
        t0 = time.perf_counter()
        if slicer is not None:
            slicer.start()
        while time.perf_counter() - t0 < ctx.seconds:
            c0 = time.perf_counter()
            with spans("call"):
                reported.append(call(next(seeds)))
            walls.append(time.perf_counter() - c0)
            execs.append(float(sj.last_run_obs()["exec_s"]))
        if slicer is not None:
            slicer.join()
        ctx.compiles.active = False
        outs = list(rec.outs)
    window_s = time.perf_counter() - t0
    misses = sj.cache_info().misses - miss0
    mem_peak = harness.memory_peak(ctx.devices)
    n_calls = len(walls)
    e2e = {"sim_req_per_s": n_req * n_points * n_calls / sum(walls),
           "setup_s": setup_s}
    ctx.log(f"window {window_s:.2f}s: {n_calls} calls x {n_points} points x "
            f"{n_req} requests, {trace['max_ticks']} ticks; program time "
            f"{sum(execs):.3f}s; {ctx.compiles.n} backend compiles, "
            f"{misses} program-cache misses in the window; e2e {e2e}")

    # free the device's copies, then check every answer of every call
    outs = [{k: np.asarray(o[k]) for k in ANSWERS} for o in outs]
    gc.collect()
    checks, failed, extra = check(ctx, cfg, trace, arrival, gen, points,
                                  outs, reported)
    extra.update(window_compiles=ctx.compiles.n,
                 window_program_cache_misses=misses, calls=n_calls,
                 points=n_points)
    out = {"e2e": e2e, "checks": checks, "attempted": n_calls * n_points,
           "failed": failed, "memory_peak_bytes": mem_peak, "extra": extra}
    if ctx.trace:
        red = tr.reduce_trace(ctx.tracer.file())
        ctx.tracer.save(red)
        layer = {"trace": red, "walls_s": walls, "execs_s": execs,
                 "ticks": int(trace["max_ticks"]), "points": n_points}
        out.update(layer_data=layer, busy_s=red["busy_s"],
                   window_s=red["window_s"], breakdown=tr.breakdown(red))
    return out


def check(ctx, cfg, trace, arrival, gen, points, outs, reported):
    """Every answer of every call against the guarantees and the recount of
    its summary, and each grid point's answers against the reference's
    trajectory of the same policy setting (one per distinct point: the
    calls differ only in their simulation seed). With ``ctx.control`` the
    control's answers take the program's place."""
    t0 = time.perf_counter()
    horizon = int(trace["max_ticks"])
    refs = [ref.simulate(arrival, gen, trace["pinned"], horizon,
                         ctx.config["fleet"], threshold=thr,
                         max_transient=k, max_slots=cfg.max_slots,
                         drain_preference=ctx.config["drain_preference"],
                         seed=[ctx.seed, 5, i])
            for i, (thr, k) in enumerate(points)]
    ref_s = time.perf_counter() - t0
    worst = {"violations": 0.0, "summary_gap": 0.0, "wait_gap": 0.0,
             "p50_wait_gap": 0.0, "done_gap": 0.0, "transients_gap": 0.0,
             "unfinished": 0.0}
    failed = 0
    for out, rep in zip(outs, reported):
        for i in range(len(points)):
            pt = ({k: v[i] for k, v in out.items()} if len(points) > 1
                  else out)
            if ctx.control:
                pt = dict(pt, **ref.control_answers(arrival, gen, horizon),
                          busy=np.zeros_like(pt["cap"]))
                rep_i = ref.summary(arrival, pt["start"], pt["finish"],
                                    cfg.tick_s)
            else:
                rep_i = rep[i]
            c = ref.check_point(arrival, gen, pt, rep_i, cfg.tick_s)
            c.update(ref.policy_gaps(arrival, gen, pt, refs[i]))
            failed += int(c["violations"] > 0)
            worst = {k: max(worst[k], c[k]) for k in worst}
    # a mix may hold limits of its own: a cube's grid reaches policy
    # settings whose trajectories lie further from the reference's
    lim = {**ctx.config["limits"], **ctx.traffic.get("limits", {})}
    checks = {k: (worst[k], float(lim[k])) for k in lim}
    ctx.log(f"reference: {len(points)} trajectories in {ref_s:.2f}s, "
            f"{len(outs)} calls checked in {time.perf_counter() - t0:.2f}s; "
            f"worst {worst}")
    extra = {"unfinished_most": worst["unfinished"], "reference_s": ref_s}
    return checks, failed, extra


def trace_slice(jax, tracer, seconds: float) -> None:
    """Mark the traced slice as the window, then end the trace."""
    with jax.profiler.TraceAnnotation("bench.window"):
        time.sleep(seconds)
    tracer.stop()

