"""Program spans and named scopes in a profiler trace (``.xplane.pb``): what
the program's own instrumentation lets the per-layer metrics read.

The program marks its host work with spans (``repro.obs.span``:
``batcher.*``, ``serving_jax.*``) and its device work with named scopes
(``jax.named_scope``: ``kv_write``, ``layer_scan``, ``tick.route``, ...).
A span is a host event of the trace, beside the harness's ``bench.*``
spans and on the device's clock. A scope is part of each HLO
instruction's ``op_name``, which the trace keeps in the metadata of each
op's event (its ``tf_op`` stat) where ``ProfileData`` does not show it:
:func:`op_names` reads it from the file. An op with no ``op_name`` takes
the scope of the op event it runs inside (a scan's ``while``).

:func:`reduce_scopes` returns, beside the window and the programs' device
time as ``bench.lib.trace`` takes them:

- ``spans``: the intervals (ns) of every ``bench.*``, ``batcher.*`` and
  ``serving_jax.*`` host span that lies wholly inside the window, by name
  without TraceMe's ``#k=v#`` suffix;
- ``idle_by_span_s``: each stretch of the window in which no op ran, put to
  the innermost span open over it (a gap that crosses a span's edge is
  split there; ``none`` where no span was open: a span still open when the
  trace stops is not in it); ``idle_in_programs_s`` the part of the idle
  time that falls inside a program's run on the device;
- ``scopes_s``: per program, each op's self time under the innermost named
  scope of its ``op_name``, ``unscoped`` for the rest; ``unscoped_ops_s``
  the largest unscoped ops by stable name;
- ``phase_share``: each ``tick.*`` scope's share of the fleet programs'
  device time, in percent;
- ``dispatch_lag_s``: the median and the least lag from a
  ``batcher.dispatch`` start to the start of the decode program it
  launched, device clock less host clock (the least bounds their offset).

:func:`for_reading` is the readers' way in: it finds this run's trace
beside the reduction the harness handed them, reduces it once a run, and
keeps the result beside the trace as ``scopes.json``.
"""

from __future__ import annotations

import bisect
import json
import pathlib
import re
import statistics
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Tuple

from bench.lib.trace import (_clip, _is_device, _union, module_name,
                             self_times, stable_op)

ROOT = pathlib.Path(__file__).resolve().parents[2]

#: the host spans read here: the harness's and the program's
SPAN_PREFIXES = ("bench.", "batcher.", "serving_jax.")
#: the program a ``batcher.dispatch`` launches
DECODE = "jit_decode"
#: a fleet program's phases are the scopes named ``tick.*``
PHASE = "tick."

#: name-stack entries JAX writes itself; never a named scope
_STRUCTURAL = frozenset({"while", "body", "cond", "closed_call", "core_call",
                         "remat", "checkpoint", "rematted_computation",
                         "custom_jvp_call", "custom_vjp_call", "shard_map",
                         "body_pred"})
_SCOPE = re.compile(r"^[A-Za-z_][A-Za-z0-9_.]*$")
_BRANCH = re.compile(r"^branch_\d+_fun$")

Interval = Tuple[int, int]


def innermost_scope(op_name: str) -> Optional[str]:
    """The innermost named scope of an HLO ``op_name`` (``jit(f)/layer_scan
    /while/body/closed_call/layer_body/kv_write/scatter`` -> ``kv_write``);
    the last entry is the primitive. A fused op's names are joined by
    ``;``: the first is its root's."""
    parts = op_name.split(";", 1)[0].split("/")[:-1]
    for p in reversed(parts):
        if (_SCOPE.match(p) and p not in _STRUCTURAL
                and not _BRANCH.match(p)):
            return p
    return None


def _varint(b, i: int) -> Tuple[int, int]:
    r = shift = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return r, i


def _fields(b) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for the rest."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")
        yield key >> 3, v


def _map(entry) -> Tuple[int, object]:
    f = dict(_fields(entry))
    return f.get(1, 0), f.get(2, b"")


def op_names(path) -> Dict[str, Dict[str, str]]:
    """Per device plane of the trace: each op event's name (its HLO
    instruction's text) -> the instruction's ``op_name``, from the ``tf_op``
    stat of the event's metadata. Reads the XSpace message
    (``tsl/profiler/protobuf/xplane.proto``: planes 1; a plane's name 2,
    event metadata 4 and stat metadata 5, both maps from id; an event
    metadata's name 2 and stats 5; a stat's metadata id 1 and its string 5
    or interned string, a stat metadata's id, 7)."""
    raw = memoryview(pathlib.Path(path).read_bytes())
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(raw):
        if f != 1:
            continue
        name, events, stats = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(_map(v)[1])
            elif g == 5:
                k, meta = _map(v)
                stats[k] = bytes(dict(_fields(meta)).get(2, b"")).decode()
        if not _is_device(name):
            continue
        tf_op = {k for k, v in stats.items() if v == "tf_op"}
        ops: Dict[str, str] = {}
        for meta in events:
            ev_name, op = "", None
            for g, v in _fields(meta):
                if g == 2:
                    ev_name = bytes(v).decode()
                elif g == 5:
                    st = dict(_fields(v))
                    if st.get(1) in tf_op:
                        op = (bytes(st[5]).decode() if 5 in st
                              else stats.get(st.get(7), ""))
            if op:
                # TensorFlow's "name:type"; an HLO op has no type
                ops.setdefault(ev_name, op.rsplit(":", 1)[0])
        out[name] = ops
    return out


def op_scopes(events: List[Tuple[int, int, str]], names: Dict[str, str]
              ) -> List[Optional[str]]:
    """Each event's innermost named scope: from its ``op_name``, else that
    of the event it runs inside (events of one line nest like a call
    stack)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    scope: List[Optional[str]] = [None] * len(events)
    stack: List[int] = []
    for i in order:
        s, e, text = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        op = names.get(text)
        scope[i] = (innermost_scope(op) if op
                    else scope[stack[-1]] if stack else None)
        stack.append(i)
    return scope


def innermost_segments(spans: Dict[str, List[Interval]]
                       ) -> List[Tuple[int, int, str]]:
    """The timeline cut wherever a span opens or closes, each piece with
    the shortest span open over it."""
    iv = sorted((s, e, n) for n, v in spans.items() for s, e in v if e > s)
    cuts = sorted({t for s, e, _ in iv for t in (s, e)})
    segs, live, j = [], [], 0
    for a, b in zip(cuts, cuts[1:]):
        while j < len(iv) and iv[j][0] <= a:
            live.append(iv[j])
            j += 1
        live = [x for x in live if x[1] > a]
        if live:
            inner = min(live, key=lambda x: x[1] - x[0])
            segs.append((a, b, inner[2]))
    return segs


def attribute(gaps: List[Interval], segs: List[Tuple[int, int, str]]
              ) -> Dict[str, int]:
    """Each gap's length (ns) by the innermost span open over each part of
    it (``none`` where none was)."""
    starts = [a for a, _, _ in segs]
    out: Dict[str, int] = defaultdict(int)
    for gs, ge in gaps:
        covered = 0
        i = max(0, bisect.bisect_right(starts, gs) - 1)
        while i < len(segs) and segs[i][0] < ge:
            a, b, name = segs[i]
            part = min(b, ge) - max(a, gs)
            if part > 0:
                out[name] += part
                covered += part
            i += 1
        if ge - gs > covered:
            out["none"] += ge - gs - covered
    return dict(out)


def _intersect(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The overlaps of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def inside(kids: List[Interval], s: int, e: int) -> int:
    """Time (ns) of the intervals in ``kids`` that lie within ``[s, e]``."""
    return sum(b - a for a, b in kids if a >= s and b <= e)


def reduce_scopes(path, window_span: str = "bench.window") -> Dict:
    """Read ``path`` and return the reduction (times in seconds; spans in
    ns, on the trace's clock)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    spans: Dict[str, List[Interval]] = defaultdict(list)
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    dev_mods: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name.split("#", 1)[0]
                    if name.startswith(SPAN_PREFIXES):
                        s = int(ev.start_ns)
                        spans[name].append((s, s + int(ev.duration_ns)))
        elif _is_device(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                target = (mods if line.name == "XLA Modules" else
                          ops if line.name == "XLA Ops" else None)
                if target is None:
                    continue
                for ev in line.events:
                    s = int(ev.start_ns)
                    target.append((s, s + int(ev.duration_ns), ev.name))
            if ops or mods:
                dev_ops[plane.name] = ops
                dev_mods[plane.name] = mods
    if not dev_ops:
        raise ValueError(f"no device events in {path}")
    if spans.get(window_span):
        lo = min(s for s, _ in spans[window_span])
        hi = max(e for _, e in spans[window_span])
    else:
        lo = min(s for ops in dev_ops.values() for s, _, _ in ops)
        hi = max(e for ops in dev_ops.values() for _, e, _ in ops)
    n_dev = len(dev_ops)

    named_of = {p: sorted((s, e, module_name(n)) for s, e, n in mods
                          if e > lo and s < hi)
                for p, mods in dev_mods.items()}
    names = op_names(path)
    scopes_s: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    unscoped: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    programs_s: Dict[str, float] = defaultdict(float)
    programs_n: Dict[str, int] = defaultdict(int)
    busy = in_programs = 0
    gaps: List[Interval] = []
    for plane, ops in dev_ops.items():
        named = named_of[plane]
        for s, e, n in named:
            programs_s[n] += (min(e, hi) - max(s, lo)) / 1e9 / n_dev
            programs_n[n] += 1
        starts = [s for s, _, _ in named]
        in_scope = op_scopes(ops, names.get(plane, {}))
        for (s, e, text), own, scope in zip(ops, self_times(ops), in_scope):
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = named[i][2] if i >= 0 and named[i][1] > s else "-"
            scopes_s[prog][scope or "unscoped"] += own / 1e9 / n_dev
            if scope is None:
                unscoped[prog][stable_op(text)] += own / 1e9 / n_dev
        u = _union(_clip([(s, e) for s, e, _ in ops]
                         if ops else [(s, e) for s, e, _ in dev_mods[plane]],
                         lo, hi))
        busy += sum(e - s for s, e in u)
        t, own_gaps = lo, []
        for s, e in u:
            if s > t:
                own_gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            own_gaps.append((t, hi))
        gaps += own_gaps
        runs = _union([(max(s, lo), min(e, hi)) for s, e, _ in named])
        in_programs += sum(e - s for s, e in _intersect(own_gaps, runs))

    program_spans = {k: v for k, v in spans.items() if k != window_span}
    idle = attribute(gaps, innermost_segments(program_spans))
    within = {k: sorted((s, e) for s, e in v if s >= lo and e <= hi)
              for k, v in program_spans.items()}
    fleet = [p for p, sc in scopes_s.items()
             if any(k.startswith(PHASE) for k in sc)]
    fleet_s = sum(sum(scopes_s[p].values()) for p in fleet)
    phase: Dict[str, float] = defaultdict(float)
    for p in fleet:
        for k, v in scopes_s[p].items():
            if k.startswith(PHASE):
                phase[k] += 100.0 * v / fleet_s
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n_dev,
        "n_devices": n_dev,
        "programs_s": dict(programs_s),
        "programs_n": dict(programs_n),
        "spans": within,
        "idle_by_span_s": {k: v / 1e9 / n_dev for k, v in idle.items()},
        "idle_in_programs_s": in_programs / 1e9 / n_dev,
        "scopes_s": {p: dict(v) for p, v in scopes_s.items()},
        "unscoped_ops_s": {p: dict(sorted(v.items(), key=lambda kv: -kv[1])
                                   [:10]) for p, v in unscoped.items()},
        "phase_share": dict(phase),
        "dispatch_lag_s": dispatch_lag(within.get("batcher.dispatch", []),
                                       named_of),
    }


def dispatch_lag(dispatches: List[Interval],
                 named_of: Dict[str, List[Tuple[int, int, str]]]
                 ) -> Optional[Dict[str, float]]:
    """Median and least lag from each dispatch's start to the start of the
    nearest decode program on the device."""
    starts = sorted(s for named in named_of.values() for s, _, n in named
                    if n.startswith(DECODE))
    lags = []
    for d, _ in dispatches:
        i = bisect.bisect_left(starts, d)
        near = [starts[j] for j in (i - 1, i) if 0 <= j < len(starts)]
        if near:
            m = min(near, key=lambda x: abs(x - d))
            lags.append((m - d) / 1e9)
    if not lags:
        return None
    return {"median": statistics.median(lags), "min": min(lags),
            "n": len(lags)}


def for_reading(d: Dict) -> Optional[Dict]:
    """The reduction of the trace behind ``d["trace"]`` (the harness's own
    reduction, in the data every reader is handed), or ``None`` where no
    such trace is found; kept in ``d``, so the readers of one run reduce
    the trace once. The trace is the newest under
    ``<checkout>/.bench_traces/``, and it has to be the one the harness
    read: its window is the same."""
    if "scopes" not in d:
        d["scopes"] = _reduce_for(d.get("trace"))
    return d["scopes"]


def _reduce_for(red: Optional[Dict]) -> Optional[Dict]:
    if red is None:
        return None
    found = sorted((ROOT / ".bench_traces").glob("*/**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    out = reduce_scopes(found[-1])
    if abs(out["window_s"] - red["window_s"]) > 1e-6:
        return None
    cell_dir = ROOT / ".bench_traces" / found[-1].relative_to(
        ROOT / ".bench_traces").parts[0]
    keep = {k: v for k, v in out.items() if k != "spans"}
    keep["span_count"] = {k: len(v) for k, v in out["spans"].items()}
    (cell_dir / "scopes.json").write_text(json.dumps(keep, indent=1))
    return out
