"""The reduction of program spans and named scopes (``bench/lib/scopes.py``)
and the per-layer metrics that read it.

The fixture ``data/scopes_v5e.xplane.pb`` was recorded on a TPU v5 lite by
``data/record_scopes_v5e.py``: three calls of one jitted program
(``jit_decode_step``: a ``lax.scan`` of four layers, under the named
scopes ``layer_scan``, ``layer_body`` and ``kv_write``), each inside the
engine's host spans (``batcher.step`` holding ``dispatch``, ``readback``
and ``bookkeep``) and a ``bench.step``, each followed by a ``bench.idle``
sleep, all inside ``bench.window``. ``data/scopes_v5e.hlo.txt`` is the HLO
text of the executable that ran them."""

import json
import re

import pytest

from bench.lib import harness
from bench.lib import scopes as sc
from bench.lib import trace as tr
from bench.tests.tiny_cells import ROOT

DATA = ROOT / "bench" / "tests" / "data"
TRACE = DATA / "scopes_v5e.xplane.pb"
HLO = (DATA / "scopes_v5e.hlo.txt").read_text()


PROGRAM = "jit_decode_step"


@pytest.fixture(scope="module")
def red():
    return sc.reduce_scopes(TRACE)


@pytest.mark.parametrize("op_name,scope", [
    ("jit(decode_paged)/layer_scan/while/body/closed_call/layer_body/"
     "kv_write/jit(remainder)/rem", "kv_write"),
    ("jit(decode_paged)/layer_scan/while", "layer_scan"),
    ("jit(decode_paged)/layer_scan/while/body/dynamic_slice", "layer_scan"),
    ("jit(decode_paged)/layer_scan/while/body/closed_call/layer_body/"
     "paged_attention/bkgqs,bskh->bqkgh/dot_general", "paged_attention"),
    ("jit(fleet_point)/while/body/closed_call/tick.route/cond/branch_1_fun/"
     "while/body/jit(_threefry_fold_in)/_simulate.<locals>.step/while/body/"
     "closed_call/add", "tick.route"),
    ("jit(fleet_cube_vmap)/while/body_pred/tick.hedge/reduce_or",
     "tick.hedge"),
    ("layer_body/paged_attention/broadcast_in_dim;layer_body/kv_mask/ge",
     "paged_attention"),
    ("jit(fleet_point)/while/body/closed_call/add", None),
    ("x", None),
])
def test_innermost_scope(op_name, scope):
    assert sc.innermost_scope(op_name) == scope


def test_ops_without_an_op_name_take_the_scope_they_run_inside():
    events = [(0, 100, "%while"), (10, 30, "%copy.3"), (40, 90, "%fusion.1"),
              (50, 60, "%nested"), (200, 210, "%copy")]
    names = {"%while": "jit(f)/layer_scan/while",
             "%fusion.1": "jit(f)/layer_scan/while/body/layer_body/kv_write/"
                          "dynamic_update_slice"}
    assert sc.op_scopes(events, names) == [
        "layer_scan", "layer_scan", "kv_write", "kv_write", None]


def test_the_traces_op_names_are_the_executables():
    """Each op's ``op_name`` read from the trace's event metadata is the one
    the executable's HLO text gives its instruction; a copy the compiler put
    in a scan's body, with none of its own, carries its loop's."""
    hlo = dict(re.findall(r'^\s+(?:ROOT )?%([^\s=]+) = .*op_name="([^"]*)"',
                          HLO, re.M))
    ops = {re.match(r"^%([^\s=]+)", text).group(1): op
           for text, op in sc.op_names(TRACE)["/device:TPU:0"].items()}
    own = {i: op for i, op in ops.items() if i in hlo}
    assert len(own) >= 3 and all(hlo[i] == op for i, op in own.items())
    assert {sc.innermost_scope(op) for op in own.values()} >= {
        "kv_write", "layer_body"}
    inserted = set(ops) - set(hlo)
    assert inserted and all(ops[i] == "jit(decode_step)/layer_scan/while"
                            for i in inserted)


def test_fixture_ops_map_to_their_scopes(red):
    s = red["scopes_s"][PROGRAM]
    assert {"kv_write", "layer_body", "layer_scan"} <= set(s)
    assert set(s) <= {"kv_write", "layer_body", "layer_scan", "unscoped"}
    # scopes and the rest account for the program's ops; the rest of its
    # run on the device is the idle time between them
    assert sum(s.values()) == pytest.approx(red["busy_s"], rel=0.01)
    assert red["programs_s"][PROGRAM] - red["busy_s"] == pytest.approx(
        red["idle_in_programs_s"], abs=1e-8)
    assert red["phase_share"] == {}


def test_fixture_window_and_programs_agree_with_the_trace_reduction(red):
    base = tr.reduce_trace(TRACE)
    for k in ("window_s", "busy_s", "programs_s", "programs_n"):
        assert red[k] == pytest.approx(base[k]), k


def test_fixture_idle_goes_to_the_innermost_span(red):
    idle = red["idle_by_span_s"]
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], abs=1e-9)
    # the program's spans now hold what bench.step held
    assert idle.get("batcher.bookkeep", 0) > 0
    assert set(idle) <= {"batcher.dispatch", "batcher.readback",
                         "batcher.bookkeep", "batcher.step", "bench.step",
                         "bench.idle", "none"}
    assert idle.get("bench.step", 0) < idle["batcher.bookkeep"]


def test_fixture_spans_and_dispatch_lag(red):
    spans = red["spans"]
    n = len(spans["batcher.step"])
    assert n >= 2
    for kid in ("batcher.dispatch", "batcher.readback", "batcher.bookkeep"):
        assert len(spans[kid]) == n
    lag = red["dispatch_lag_s"]
    assert lag["n"] == n and lag["min"] <= lag["median"] < 0.005
    assert red["programs_n"][PROGRAM] >= n - 1


def test_idle_is_split_where_a_span_closes():
    spans = {"bench.step": [(0, 100)], "batcher.dispatch": [(10, 40)],
             "batcher.bookkeep": [(60, 90)]}
    segs = sc.innermost_segments(spans)
    got = sc.attribute([(20, 70), (95, 120)], segs)
    assert got == {"batcher.dispatch": 20, "bench.step": 25,
                   "batcher.bookkeep": 10, "none": 20}


def test_dispatch_lag_pairs_each_dispatch_with_the_nearest_decode():
    named = {"/device:TPU:0": [(1_500_000, 5_000_000, "jit_decode_paged"),
                               (10_000_000, 13_000_000, "jit_decode_paged"),
                               (20_000_000, 21_000_000, "jit_prefill")]}
    lag = sc.dispatch_lag([(1_000_000, 1_200_000), (9_000_000, 9_100_000)],
                          named)
    assert lag == pytest.approx({"median": 0.00075, "min": 0.0005, "n": 2})
    assert sc.dispatch_lag([], named) is None


def _read(name, d):
    return harness.load_module(ROOT / "bench" / "metrics"
                               / f"{name}.py").read(d)


SYNTHETIC = {
    "spans": {"batcher.step": [(0, 10_000_000), (20_000_000, 24_000_000)],
              "batcher.readback": [(5_000_000, 9_000_000),
                                   (21_000_000, 23_000_000)],
              "batcher.admit": [(1_000_000, 2_000_000),
                                (30_000_000, 130_000_000)]},
    "programs_n": {"jit_decode_paged": 4},
    "scopes_s": {"jit_decode_paged": {"kv_write": 0.006, "kv_mask": 0.002,
                                      "layer_scan": 0.08,
                                      "paged_attention": 0.3,
                                      "unscoped": 0.01}},
    "phase_share": {"tick.advance": 31.5, "tick.route": 22.25},
}


@pytest.mark.parametrize("name,value", [
    # (10 - 1 - 4) + (4 - 2) ms over two steps
    ("engine.step_host_ms", 3.5),
    ("engine.admit_ms_per_request", 50.5),
    ("model.kv_write_ms_per_step", 2.0),
    ("model.scan_carry_ms_per_step", 20.0),
    ("fleet.advance_share", 31.5),
    ("fleet.route_share", 22.25),
])
def test_readers_on_a_synthetic_reduction(monkeypatch, name, value):
    monkeypatch.setattr(sc, "for_reading", lambda d: SYNTHETIC)
    assert _read(name, {}) == pytest.approx(value)


READERS = ["engine.step_host_ms", "engine.admit_ms_per_request",
           "model.kv_write_ms_per_step", "model.scan_carry_ms_per_step",
           "fleet.advance_share", "fleet.route_share"]


@pytest.mark.parametrize("name", READERS)
def test_readers_return_none_without_spans_or_scopes(monkeypatch, name):
    assert _read(name, {}) is None  # no trace handed over
    bare = {"spans": {}, "programs_n": {"jit_decode_paged": 4},
            "scopes_s": {"jit_decode_paged": {"unscoped": 0.4}},
            "phase_share": {}}
    monkeypatch.setattr(sc, "for_reading", lambda d: bare)
    assert _read(name, {}) is None


def test_for_reading_finds_this_runs_trace_and_keeps_the_result(
        monkeypatch, tmp_path):
    cell = tmp_path / ".bench_traces" / "cell"
    (cell / "plugins").mkdir(parents=True)
    (cell / "plugins" / "t.xplane.pb").write_bytes(TRACE.read_bytes())
    monkeypatch.setattr(sc, "ROOT", tmp_path)
    base = tr.reduce_trace(TRACE)
    d = {"trace": base}
    got = sc.for_reading(d)
    assert got is not None and got["window_s"] == base["window_s"]
    assert sc.for_reading(d) is got  # once a run
    kept = json.loads((cell / "scopes.json").read_text())
    assert kept["span_count"]["batcher.step"] == len(
        got["spans"]["batcher.step"])
    # another run's reduction: the trace found is not its own
    assert sc.for_reading({"trace": dict(base, window_s=1.0)}) is None
