"""Reduction of a JAX profiler trace (``.xplane.pb``) to device metrics.

- the window: the harness's ``bench.window`` host span (else the span of
  all device events);
- busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices traced;
- per-program device time: events of the device's ``XLA Modules`` line, by
  program name without its unique id (``jit_decode_paged(12)`` ->
  ``jit_decode_paged``);
- per-op device time by stable name: the HLO instruction's opcode and its
  name without the numeric suffix (``%fusion.123 = ... fusion(...)`` ->
  ``fusion:fusion``; a Pallas kernel is a ``custom-call``), under its
  program's name; an op's time is its self time, less the ops nested in it
  (a layer scan's ``while`` holds its body's ops);
- idle gaps: the stretches of the window in which no operation ran, each
  put to the innermost ``bench.*`` host span open at its middle (``none``
  where no span was open).
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

_SUFFIX = re.compile(r"[._]\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
#: ``%name.12 = <shape> opcode(operands), attrs``: the shape's own
#: parentheses follow no space, so the first `` word(`` is the opcode
_HLO = re.compile(r"^%?([^\s=]+)\s*=\s.*?\s([a-z][a-z0-9_-]*)\(")

Interval = Tuple[int, int]


def stable_op(text: str) -> str:
    m = _HLO.match(text)
    name, opcode = (m.group(1), m.group(2)) if m else (text.split()[0], "")
    while _SUFFIX.search(name):
        name = _SUFFIX.sub("", name)
    return f"{opcode}:{name}" if opcode else name


def self_times(events: List[Tuple[int, int, str]]) -> List[int]:
    """Each event's duration less that of the events nested in it (events
    of one line nest like a call stack)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, _ in events]
    stack: List[int] = []
    for i in order:
        s, e, _ = events[i]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name).strip()


def _union(iv: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(iv):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(iv: List[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:") and "CPU" not in plane_name


def reduce_trace(path, window_span: str = "bench.window") -> Dict:
    """Read ``path`` and return the reduction (times in seconds)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    host: Dict[str, List[Interval]] = defaultdict(list)
    dev_ops: Dict[str, List[Tuple[int, int, str]]] = {}
    dev_mods: Dict[str, List[Tuple[int, int, str]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        host[ev.name].append((s, s + int(ev.duration_ns)))
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
    if host.get(window_span):
        lo = min(s for s, _ in host[window_span])
        hi = max(e for _, e in host[window_span])
    else:
        lo = min(s for ops in dev_ops.values() for s, _, _ in ops)
        hi = max(e for ops in dev_ops.values() for _, e, _ in ops)
    n_dev = len(dev_ops)
    busy = 0
    gaps: List[Interval] = []
    ops_s: Dict[str, float] = defaultdict(float)
    mods_s: Dict[str, float] = defaultdict(float)
    mods_n: Dict[str, int] = defaultdict(int)
    mod_iv: Dict[str, List[Interval]] = defaultdict(list)
    for plane, ops in dev_ops.items():
        # an op's program: the module event that holds its start
        mods = sorted(_clip([(s, e) for s, e, _ in dev_mods[plane]], lo, hi))
        named = sorted((s, e, module_name(n)) for s, e, n in dev_mods[plane]
                       if e > lo and s < hi)
        for s, e, n in named:
            mods_s[n] += (min(e, hi) - max(s, lo)) / 1e9 / n_dev
            mods_n[n] += 1
            mod_iv[n].append((max(s, lo), min(e, hi)))
        starts = [s for s, _, _ in named]

        for (s, e, n), own in zip(ops, self_times(ops)):
            if e <= lo or s >= hi:
                continue
            i = bisect.bisect_right(starts, s) - 1
            prog = named[i][2] if i >= 0 and named[i][1] > s else "-"
            ops_s[f"{prog}/{stable_op(n)}"] += own / 1e9 / n_dev
        u = _union(_clip([(s, e) for s, e, _ in ops] if ops else mods,
                         lo, hi))
        busy += sum(e - s for s, e in u)
        t = lo
        for s, e in u:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if hi > t:
            gaps.append((t, hi))
    spans = {k: sorted(v) for k, v in host.items() if k != window_span}
    starts_of = {k: [a for a, _ in v] for k, v in spans.items()}
    idle_by: Dict[str, float] = defaultdict(float)
    for s, e in gaps:
        mid = (s + e) // 2
        owner, width = "none", None
        for name, iv in spans.items():
            # spans of one name do not nest: the last one opened by ``mid``
            i = bisect.bisect_right(starts_of[name], mid) - 1
            if i >= 0 and mid < iv[i][1] and (width is None
                                              or iv[i][1] - iv[i][0] < width):
                owner, width = name, iv[i][1] - iv[i][0]
        idle_by[owner] += (e - s) / 1e9 / n_dev
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy / 1e9 / n_dev,
        "n_devices": n_dev,
        "programs_s": dict(mods_s),
        "programs_n": dict(mods_n),
        "program_intervals": {k: v for k, v in mod_iv.items()},
        "ops_s": dict(ops_s),
        "idle_by_span_s": dict(idle_by),
        "host_spans": {k: [(max(a, lo), min(b, hi)) for a, b in v
                           if b > lo and a < hi] for k, v in host.items()},
    }


def program_time(red: Dict, prefix: str) -> float:
    """Device seconds of the programs whose name starts with ``prefix``."""
    return sum(v for k, v in red["programs_s"].items()
               if k.startswith(prefix))


def op_time(red: Dict, program_prefix: str, op_pattern: str) -> float:
    """Device seconds of ops matching ``op_pattern`` (a regex on the stable
    op name) inside programs named ``program_prefix``*."""
    pat = re.compile(op_pattern)
    total = 0.0
    for k, v in red["ops_s"].items():
        prog, _, op = k.partition("/")
        if prog.startswith(program_prefix) and pat.search(op):
            total += v
    return total


def breakdown(red: Dict, top: int = 10) -> Dict:
    ops = sorted(red["ops_s"].items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(red["idle_by_span_s"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in idle]}
