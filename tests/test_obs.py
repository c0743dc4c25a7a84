"""Flight-recorder tests (``repro.obs``): the typed scheduler event log,
the Perfetto tracer, and the metrics registry.

  * cross-engine contract: the deterministic ``serve_*`` presets (one
    on-demand replica, at most one transient, no revocations) produce
    *identical* per-tick event streams on the Python serving oracle and
    the JAX engine — the event log is a debugging diff, so it must agree
    wherever the metrics agree bit-exactly;
  * event conservation: RENT/PROVISION/DRAIN/REVOKE pair up on every
    engine (DES, serving, serving_jax), tied to independently observed
    fleet end-state where available;
  * the tracer's disabled path allocates (almost) nothing — engines keep
    ``tracer=None`` / ``enabled=False`` in the hot loop, so the overhead
    bound is part of the contract;
  * trace exports pass the structural schema check (and the check catches
    deliberately broken files);
  * RunResult validation gates the new telemetry: negative wall times,
    serving_jax results without ``meta["obs"]`` / ``meta["fleet_spec"]``;
  * the program's profiler spans (``obs.span``) nest as the engine's step
    does, and the fleet program carries one named scope per tick phase;
  * the smoke driver persists a machine-readable ``smoke_summary.json``.
"""

import dataclasses
import json
import tracemalloc

import numpy as np
import pytest

from repro.exp import (CANONICAL_METRICS, REQUIRED_SERIES, RunResult,
                       validate_run_result)
from repro.obs import (ADMIT, DRAIN, EVENT_TYPES, HEDGE, HEDGE_WIN,
                       PROVISION, RENT, THROTTLE, EventRecorder,
                       MetricsRegistry,
                       Tracer, check_replica_lifecycles,
                       check_transient_conservation, diff_event_streams,
                       events_from_counts, span, trace_from_run_result,
                       validate_trace_events, validate_trace_file)
from repro.runtime import serving_jax as sj
from repro.runtime.serving import (ElasticServingFleet, Request,
                                   ServingFleetConfig)

# ------------------------------------------------------------ event schema


def test_event_type_order_is_the_on_disk_schema():
    # column order is load-bearing: serving_jax emits its per-tick event
    # vector in exactly this order, and persisted event_counts series
    # decode against it — append-only, never reorder. THROTTLE is the
    # tenth column (PR 8's nine->ten migration): event_counts arrays
    # persisted before it decode fine because columns only appended
    assert EVENT_TYPES == ("RENT", "PROVISION", "DRAIN", "REVOKE", "HEDGE",
                           "HEDGE_WIN", "ADMIT", "DISPLACE", "REROUTE",
                           "THROTTLE")
    assert (RENT, PROVISION, DRAIN, ADMIT, THROTTLE) == (0, 1, 2, 6, 9)


def test_recorder_counts_roundtrip():
    rec = EventRecorder()
    rec.emit(0, RENT)
    rec.emit(3, PROVISION, replica=7)
    rec.emit(3, ADMIT, replica=7, rid=2)
    rec.emit(9, DRAIN, replica=7)
    rec.emit(9, ADMIT, count=3)
    counts = rec.counts(10)
    assert counts.shape == (10, len(EVENT_TYPES))
    assert int(counts.sum()) == len(rec) == 7
    back = events_from_counts(counts)
    assert back.type_counts() == rec.type_counts()
    assert diff_event_streams(rec, back) == []
    assert diff_event_streams(rec, counts[:4]) != []  # truncated stream


def test_events_from_counts_rejects_bad_shape():
    with pytest.raises(ValueError):
        events_from_counts(np.zeros((5, 3)))


def test_empty_recorder_counts_zero_events():
    # a run that never emits: counts must be an all-zero (T, N) array and
    # reconstruct to an empty log, not crash on the empty event list
    rec = EventRecorder()
    counts = rec.counts(5)
    assert counts.shape == (5, len(EVENT_TYPES))
    assert int(counts.sum()) == 0
    back = events_from_counts(counts)
    assert len(back) == 0 and back.events == []
    assert diff_event_streams(rec, back, horizon=5) == []


def test_zero_tick_run_counts_and_decode():
    # horizon 0 (a zero-tick run) is a legal degenerate: (0, N) counts,
    # zero decoded events, and events at t>=horizon are dropped
    rec = EventRecorder()
    rec.emit(0, RENT)  # at/after horizon 0 -> dropped by counts(0)
    counts = rec.counts(0)
    assert counts.shape == (0, len(EVENT_TYPES))
    back = events_from_counts(counts)
    assert len(back) == 0
    assert back.type_counts() == {name: 0 for name in EVENT_TYPES}
    assert events_from_counts(np.zeros((0, len(EVENT_TYPES)))).events == []


def test_conservation_and_lifecycle_checks_flag_violations():
    rec = EventRecorder()
    rec.emit(0, PROVISION, replica=1)  # PROVISION without RENT
    rec.emit(2, DRAIN, replica=1)
    rec.emit(5, DRAIN, replica=1)      # second end for the same replica
    assert any("PROVISION" in p
               for p in check_transient_conservation(rec))
    assert any("after" in p for p in check_replica_lifecycles(rec))
    ok = EventRecorder()
    ok.emit(0, RENT)
    ok.emit(3, PROVISION, replica=1)
    ok.emit(8, DRAIN, replica=1)
    assert check_transient_conservation(ok, n_online_end=0,
                                        n_pending_end=0) == []
    assert check_replica_lifecycles(ok) == []


# --------------------------------------------- cross-engine event streams
#
# Same deterministic presets as tests/test_serving_jax.py's bit-exact
# metric tests: one on-demand replica, at most one transient, mttf=0 —
# no random probing choice, no revocation, so the serving oracle and the
# JAX engine must produce identical per-tick event streams.

_DET_CASES = [
    (ServingFleetConfig(n_replicas=1, max_transient=0, threshold=0.5,
                        provisioning_delay=3.0, tick_s=1.0),
     [Request(0, 0, 3), Request(1, 0, 2), Request(2, 4, 1)],
     np.zeros(30, int), 30),
    (ServingFleetConfig(n_replicas=1, max_transient=1, threshold=0.5,
                        provisioning_delay=3.0, tick_s=1.0),
     [Request(0, 0, 3), Request(1, 2, 4), Request(2, 6, 2),
      Request(3, 8, 3), Request(4, 12, 2), Request(5, 21, 1)],
     None, 40),
    (ServingFleetConfig(n_replicas=1, max_transient=1, max_slots=2,
                        threshold=0.5, provisioning_delay=3.0),
     [Request(0, 0, 3), Request(1, 2, 4), Request(2, 6, 2),
      Request(3, 8, 3), Request(4, 12, 2), Request(5, 21, 1)],
     None, 40),
]


def _pin(case_pin, T):
    if case_pin is not None:
        return case_pin
    pin = np.zeros(T, int)
    pin[5:20] = 1
    return pin


def _py_events(cfg, reqs_proto, pin, max_ticks):
    reqs = [Request(q.rid, q.arrival, q.gen_len, job_id=q.job_id)
            for q in reqs_proto]
    rec = EventRecorder()
    fleet = ElasticServingFleet.from_config(cfg, seed=0, recorder=rec)
    fleet.run(reqs, lambda t: int(pin[t]) if t < len(pin) else 0, max_ticks)
    return fleet, rec, reqs


@pytest.mark.parametrize("case", range(len(_DET_CASES)))
def test_serving_vs_jax_event_streams_identical(case):
    cfg, reqs, case_pin, T = _DET_CASES[case]
    pin = _pin(case_pin, T)
    fleet, rec, _ = _py_events(cfg, reqs, pin, T)
    _, series, _ = sj.run_workload(cfg, reqs, pin, T, sim_seed=0)
    diff = diff_event_streams(rec.counts(T), series["event_counts"])
    assert diff == [], diff
    # and both streams individually conserve, tied to the oracle end-state
    n_online = sum(1 for r in fleet.replicas
                   if r.kind == "transient" and r.offline_at is None)
    for log in (rec, series["event_counts"]):
        assert check_transient_conservation(
            log, n_online_end=n_online,
            n_pending_end=len(fleet.pending_online), horizon=T) == []
    assert check_replica_lifecycles(rec) == []


def test_serving_vs_jax_throttle_events_identical():
    # two tenants on the deterministic one-replica fleet: tenant 0's bucket
    # holds 5 work units and never refills, tenant 1's is effectively
    # bottomless. Tenant 0's third request is the first over-credit
    # placement, so both engines must emit THROTTLE on the same ticks —
    # the tenth event column is part of the cross-engine contract
    from repro.sched.policy import TenantGuardProbing

    cfg = ServingFleetConfig(n_replicas=1, max_transient=1, threshold=0.5,
                             provisioning_delay=3.0, tick_s=1.0)
    T = 40
    pin = np.zeros(T, int)
    pin[20:30] = 1
    rate, burst = [0.0, 0.0], [5.0, 1e9]

    def mk_reqs():
        return [Request(i, a, g, job_id=i, tenant_id=i % 2)
                for i, (a, g) in enumerate(
                    [(0, 3), (1, 2), (4, 2), (6, 3), (8, 2), (12, 3),
                     (22, 2), (24, 1), (31, 2), (33, 1)])]

    rec = EventRecorder()
    pol = TenantGuardProbing(n_tenants=2, credit_rate=rate,
                             credit_burst=burst)
    fleet = ElasticServingFleet.from_config(cfg, seed=0, recorder=rec,
                                            short_policy=pol)
    fleet.run(mk_reqs(), lambda t: int(pin[t]), T)
    _, series, _ = sj.run_workload(cfg, mk_reqs(), pin, T, sim_seed=0,
                                   n_tenants=2, credit_rate=rate,
                                   credit_burst=burst)
    assert pol.n_throttled > 0  # the gate actually fired
    diff = diff_event_streams(rec.counts(T), series["event_counts"])
    assert diff == [], diff
    assert int(series["event_counts"][:, THROTTLE].sum()) == pol.n_throttled


@pytest.mark.parametrize("seed", [0, 3])
def test_jax_event_counts_conserve_on_random_workloads(seed):
    rng = np.random.default_rng(100 + seed)
    T, n = 400, 80
    arr = np.sort(rng.integers(0, T - 20, n))
    reqs = [Request(i, int(arr[i]), int(rng.integers(1, 6)))
            for i in range(n)]
    pin = np.zeros(T, int)
    pin[50:150] = int(rng.integers(1, 3))
    cfg = ServingFleetConfig(n_replicas=2, max_transient=2, threshold=0.5,
                             provisioning_delay=3.0, tick_s=1.0)
    _, series, _ = sj.run_workload(cfg, reqs, pin, T, sim_seed=seed)
    ec = series["event_counts"]
    assert ec.shape == (T, len(EVENT_TYPES))
    assert check_transient_conservation(ec) == []
    totals = ec.sum(axis=0)
    assert totals[HEDGE_WIN] <= totals[HEDGE]
    assert totals[ADMIT] >= 1  # work actually flowed


def test_des_engine_emits_conserving_events():
    from repro.sched import get_scenario

    rec = EventRecorder()
    get_scenario("serve_yahoo").run(
        quick=True, seed=7, sim_seed=0, recorder=rec,
        trace_overrides=dict(n_servers=150, n_short=8, horizon=2 * 3600.0))
    assert len(rec) > 0
    assert rec.type_counts()["ADMIT"] > 0
    assert check_transient_conservation(rec) == []
    assert check_replica_lifecycles(rec) == []


# ------------------------------------------------------------------ tracer


def test_tracer_disabled_path_is_allocation_free():
    tr = Tracer(enabled=False)
    tracemalloc.start()
    base = tracemalloc.take_snapshot()
    for i in range(10_000):
        tr.complete("req", i, 1.0, tid=3)
        tr.counter("queue_depth", i, i % 7)
        tr.async_begin("transient", i, aid=i, cat="transient")
    snap = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grown = sum(s.size_diff for s in snap.compare_to(base, "lineno")
                if s.size_diff > 0)
    assert tr.events == []
    # 30k disabled calls must not accumulate anything; the bound is loose
    # (interpreter noise) but catches any per-call allocation regression
    assert grown < 16_384, f"disabled tracer grew {grown} bytes"


def test_tracer_export_passes_schema_check(tmp_path):
    tr = Tracer(tick_s=2.0)
    tr.process_name(0, "fleet")
    tr.thread_name(0, 1, "ondemand-1")
    tr.async_begin("transient", 3, aid=5, cat="transient", tid=5)
    tr.complete("req 0", 4, 2, tid=1, args={"gen_len": 2})
    tr.flow_start("hedge", 5, fid=0, tid=1)
    tr.flow_end("hedge", 5, fid=0, tid=5)
    tr.counter("queue_depth", 0, 0)
    tr.counter("queue_depth", 6, 3)
    tr.async_end("transient", 9, aid=5, cat="transient", tid=5,
                 args={"end": "drain"})
    path = tr.export(str(tmp_path / "t.trace.json"))
    assert validate_trace_file(path, require_counters=("queue_depth",),
                               require_async_cats=("transient",)) == []
    obj = json.loads((tmp_path / "t.trace.json").read_text())
    # ticks scale to microseconds through tick_s
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert xs[0]["ts"] == pytest.approx(4 * 2.0 * 1e6)


def test_trace_schema_check_catches_breakage(tmp_path):
    bad = {"traceEvents": [
        {"ph": "C", "name": "q", "pid": 0, "tid": 0, "ts": 5.0,
         "args": {"value": 1.0}},
        {"ph": "C", "name": "q", "pid": 0, "tid": 0, "ts": 1.0,
         "args": {"value": 2.0}},  # ts goes backwards on the track
        {"ph": "X", "name": "r", "pid": 0, "tid": 1, "ts": 0.0,
         "dur": -4.0},             # negative duration
        {"ph": "b", "name": "s", "pid": 0, "tid": 1, "ts": 0.0},  # no id/cat
    ]}
    problems = validate_trace_events(bad)
    assert len(problems) >= 3
    assert validate_trace_events({"nope": 1}) != []
    # and the CLI exits nonzero on it
    from repro.obs.trace import _main

    p = tmp_path / "bad.trace.json"
    p.write_text(json.dumps(bad))
    assert _main(["--check", str(p)]) == 1


def test_disabled_tracer_in_fleet_changes_nothing():
    cfg, reqs, case_pin, T = _DET_CASES[1]
    pin = _pin(case_pin, T)
    off = Tracer(enabled=False)
    fleet, _, ref_reqs = _py_events(cfg, reqs, pin, T)
    reqs2 = [Request(q.rid, q.arrival, q.gen_len) for q in reqs]
    fleet2 = ElasticServingFleet.from_config(cfg, seed=0, tracer=off)
    fleet2.run(reqs2, lambda t: int(pin[t]) if t < len(pin) else 0, T)
    assert off.events == []
    assert sorted(q.wait for q in reqs2 if q.wait is not None) == \
        sorted(q.wait for q in ref_reqs if q.wait is not None)
    assert fleet2.n_hedges == fleet.n_hedges


def test_live_tracer_records_transient_spans_and_counters():
    cfg, reqs, case_pin, T = _DET_CASES[1]
    pin = _pin(case_pin, T)
    tr = Tracer(tick_s=cfg.tick_s)
    reqs2 = [Request(q.rid, q.arrival, q.gen_len) for q in reqs]
    fleet = ElasticServingFleet.from_config(cfg, seed=0, tracer=tr)
    fleet.run(reqs2, lambda t: int(pin[t]) if t < len(pin) else 0, T)
    assert validate_trace_events(tr.to_dict(),
                                 require_counters=("queue_depth",),
                                 require_async_cats=("transient",)) == []
    phs = {e["ph"] for e in tr.events}
    assert {"b", "e", "X", "C", "M"} <= phs  # spans, slices, counters


def test_trace_from_run_result_fallback(tmp_path):
    rec = EventRecorder()
    rec.emit(2, RENT)
    rec.emit(5, PROVISION, replica=1)
    rr = _valid_rr("serving_jax")
    rr = dataclasses.replace(rr, series={**rr.series,
                                         "queue_depth": np.arange(4.0),
                                         "event_counts": rec.counts(6)})
    path = trace_from_run_result(rr, str(tmp_path / "fb.trace.json"))
    assert validate_trace_file(path,
                               require_counters=("queue_depth",)) == []


# --------------------------------------------------------- metrics registry


def test_metrics_registry_snapshot_and_kinds():
    reg = MetricsRegistry()
    reg.counter("hits").inc()
    reg.counter("hits").inc(2)
    for v in range(1, 101):
        reg.histogram("lat").observe(float(v))
    snap = reg.snapshot()
    assert snap["counters"]["hits"] == 3
    h = snap["histograms"]["lat"]
    assert h["count"] == 100 and h["p50"] == 50.0 and h["p99"] == 99.0
    with pytest.raises(TypeError):
        reg.histogram("hits")  # registered as a counter
    with span("block", rid=3):  # no profile is being taken: a no-op
        pass
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "histograms": {}}


def _profile_events(run, tmp_path):
    """Host events of a CPU profile of ``run()``: (name, start, end,
    stats) of every ``batcher.*`` / ``serving_jax.*`` span."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith(("batcher.", "serving_jax.")):
                    s = int(ev.start_ns)
                    out.append((name, s, s + int(ev.duration_ns),
                                dict(ev.stats)))
    return out


def _within(events, name, s, e):
    return [x for x in events if x[0] == name and s <= x[1] and x[2] <= e]


def test_batcher_spans_in_a_profile(tmp_path):
    """A profile of a tiny paged engine shows each decoding step's spans
    nested in ``batcher.step``, and one ``batcher.admit`` per request, with
    its ``rid``, holding the request's prefill and scatter."""
    import jax

    from repro.configs import smoke_config
    from repro.models import build_model
    from repro.runtime.batching import ContinuousBatcher, GenRequest

    cfg = smoke_config("starcoder2-3b")
    model = build_model(cfg)
    b = ContinuousBatcher(model, model.init(jax.random.PRNGKey(0)),
                          max_slots=2, max_len=64, kv_layout="paged")
    reqs = [GenRequest(rid, np.arange(1, 9, dtype=np.int32), 3)
            for rid in (11, 12, 13)]
    for r in reqs:
        b.submit(r)
    ev = _profile_events(b.run, tmp_path)
    steps = [x for x in ev if x[0] == "batcher.step"]
    assert len(steps) == b.step_count
    decoding = [x for x in steps
                if _within(ev, "batcher.dispatch", x[1], x[2])]
    assert decoding
    for _, s, e, _ in decoding:
        for kid in ("batcher.dispatch", "batcher.readback",
                    "batcher.bookkeep"):
            assert len(_within(ev, kid, s, e)) == 1, kid
    admits = [x for x in ev if x[0] == "batcher.admit"]
    assert sorted(x[3]["rid"] for x in admits) == [11, 12, 13]
    for _, s, e, _ in admits:
        assert len(_within(ev, "batcher.prefill", s, e)) == 1
        assert len(_within(ev, "batcher.scatter", s, e)) == 1
        assert any(x[1] <= s and e <= x[2] for x in steps)


def test_fleet_program_is_named_and_holds_every_phase_scope():
    import jax

    cfg, reqs, _, T = _DET_CASES[1]
    spec = sj.make_spec(cfg, n_requests=len(reqs), max_ticks=T,
                        max_arrivals_per_tick=1)
    consts = sj.build_consts(spec, reqs, np.zeros(T, int))
    text = sj.get_program(spec).lower(
        sj.make_params(cfg), consts,
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "jit_fleet_point" in text
    for phase in ("pin", "flush", "provision", "route", "control", "revoke",
                  "hedge", "advance", "record"):
        assert f"tick.{phase}/" in text, phase


def test_serving_jax_run_records_obs_telemetry():
    cfg, reqs, case_pin, T = _DET_CASES[0]
    pin = _pin(case_pin, T)
    sj.run_workload(cfg, reqs, pin, T, sim_seed=0)
    obs = sj.last_run_obs()
    assert set(obs) >= {"jit_cache", "compile", "steady"}
    total = obs["jit_cache"]["hits"] + obs["jit_cache"]["misses"]
    assert total >= 1
    assert obs["compile"]["count"] + obs["steady"]["count"] >= 1


# ------------------------------------------------- RunResult schema gating


def _valid_rr(engine="serving", scenario="serve_yahoo") -> RunResult:
    metrics = {m: 1.0 for m in CANONICAL_METRICS}
    series = {name: (np.zeros((3, len(EVENT_TYPES)))
                     if name == "event_counts" else np.arange(3.0))
              for name in REQUIRED_SERIES.get(engine, ())}
    meta = {}
    if engine == "serving_jax":
        meta = {"fleet_spec": {"n_replicas": 1},
                "obs": {"jit_cache": {"hits": 1, "misses": 1},
                        "compile": {"count": 1}, "steady": {"count": 0}}}
    return RunResult(engine=engine, scenario=scenario,
                     config={"n_replicas": 8}, overrides={},
                     metrics=metrics, series=series, seed=42, sim_seed=42,
                     meta=meta)


def test_validate_accepts_serving_jax_with_obs():
    assert validate_run_result(_valid_rr("serving_jax")) == []


@pytest.mark.parametrize("corrupt,needle", [
    (dict(wall_time_s=-0.5), "negative wall_time_s"),
    (dict(meta={"obs": {"jit_cache": {}, "compile": {}, "steady": {}}}),
     "fleet_spec"),
    (dict(meta={"fleet_spec": {"n_replicas": 1}}), "obs"),
    (dict(meta={"fleet_spec": {"n_replicas": 1}, "obs": {"jit_cache": {}}}),
     "obs"),
])
def test_validate_flags_missing_telemetry(corrupt, needle):
    rr = dataclasses.replace(_valid_rr("serving_jax"), **corrupt)
    problems = validate_run_result(rr)
    assert problems and any(needle in p for p in problems), problems


# ------------------------------------------------------- smoke summary file


def test_smoke_writes_machine_readable_summary(tmp_path):
    from repro.launch import smoke

    _valid_rr("serving").save(tmp_path / "serve_yahoo-serving.runresult.npz")
    assert smoke.main(["--validate-only", "--out-dir", str(tmp_path)]) == 0
    summary = json.loads((tmp_path / "smoke_summary.json").read_text())
    assert summary["validate_only"] is True
    assert summary["n_validated"] == 1
    assert summary["n_schema_invalid"] == 0
    assert summary["validation"][0]["engine"] == "serving"
