"""What every cell shares: finding its files by name, the device check, the
compilation cache, compile counting, tracing, and the result line."""

from __future__ import annotations

import importlib.util
import json
import math
import pathlib
import sys
import time
from contextlib import contextmanager
from types import SimpleNamespace
from typing import Dict

T_START = time.time()

#: JAX's monitoring event for a program compiled by the backend (a hit in
#: the persistent cache does not fire it)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_json(path: pathlib.Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_file_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def resolve(root: pathlib.Path, name: str) -> Dict:
    """The cell ``name`` with its configuration, traffic and the metrics it
    reports, each loaded from its own file; and, where the configuration
    names one, its reference module's path."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[name]
    conf = load_json(root / "bench" / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / "bench" / "traffic"
                        / f"{cell['traffic']}.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if name in m.get("workloads", [name] if m["moves"] in reported
                              else [])]
    reference = None
    if "reference" in conf:
        reference = root / "bench" / "reference" / f"{conf['reference']}.py"
        if not reference.is_file():
            raise FileNotFoundError(f"no such reference: {reference}")
    return {"cell": cell, "config": conf, "traffic": traffic,
            "end_to_end": e2e, "per_layer": layer,
            "system": root / "bench" / "systems" / f"{conf['system']}.py",
            "reference": reference,
            "readers": {m["name"]: root / "bench" / "metrics"
                        / f"{m['name']}.py" for m in layer}}


def peaks_for(root: pathlib.Path, kind: str) -> Dict:
    table = load_json(root / "bench" / "peaks.json")
    if kind not in table["devices"]:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


class CompileCounter:
    """Counts backend compiles while ``active``."""

    def __init__(self):
        import jax

        self.active = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == COMPILE_EVENT:
            self.n += 1


class Spans:
    """The harness's own host spans: each is timed on the host clock and
    written into the profiler's trace under ``bench.<name>``."""

    def __init__(self):
        self.total: Dict[str, float] = {}
        self.count: Dict[str, int] = {}

    @contextmanager
    def __call__(self, name: str):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench." + name):
            yield
        dt = time.perf_counter() - t0
        self.total[name] = self.total.get(name, 0.0) + dt
        self.count[name] = self.count.get(name, 0) + 1

    def reset(self) -> None:
        self.total.clear()
        self.count.clear()


class Tracer:
    """Profiler trace of part of the window, written under the checkout."""

    def __init__(self, root: pathlib.Path, name: str):
        self.dir = root / ".bench_traces" / name
        self.on = False

    def start(self) -> None:
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.dir), profiler_options=opts)
        self.on = True

    def stop(self) -> None:
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on = False

    def save(self, reduction: Dict) -> None:
        """Keep the reduction beside the trace, for reading by hand."""
        keep = {k: v for k, v in reduction.items()
                if k not in ("program_intervals", "host_spans")}
        (self.dir / "reduction.json").write_text(json.dumps(keep, indent=1))

    def file(self) -> pathlib.Path:
        found = sorted(self.dir.glob("**/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace written under {self.dir}")
        return found[-1]


def nearest_rank(values, q: float) -> float:
    """The ``q``-quantile (0 < q < 1) by nearest rank; a missing value is
    ``inf`` and sorts last."""
    v = sorted(values)
    if not v:
        return math.nan
    return float(v[min(len(v) - 1, max(0, math.ceil(q * len(v)) - 1))])


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


def _fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr)
    return 3


def run_cell(root: pathlib.Path, args) -> int:
    try:
        cell = resolve(root, args.workload)
    except (KeyError, FileNotFoundError) as e:
        return _fail(str(e))
    if not (root / "src" / "repro").is_dir():
        return _fail(f"the system under test is not in this checkout "
                     f"({root / 'src' / 'repro'} is missing)")
    if args.rate is not None:  # the knee sweep: Poisson at this rate
        cell["traffic"]["arrivals"] = {"kind": "poisson", "rate": args.rate}

    import jax

    # a fixed directory inside the checkout: the path is part of the
    # cache's key, and the two sides of a comparison share nothing
    jax.config.update("jax_compilation_cache_dir", str(root / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    chips = int(cell["cell"]["chips"])
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        return _fail(f"cell {args.workload} needs {chips} TPU chip(s); JAX "
                     f"found {len(devs)} {devs[0].platform} device(s) "
                     f"({devs[0].device_kind})")
    devs = devs[:chips]
    try:
        peaks = peaks_for(root, devs[0].device_kind)
    except KeyError as e:
        return _fail(str(e))
    ctx = make_context(root, args, cell, devs, peaks)
    system = load_module(cell["system"])
    out = system.run(ctx)
    return emit(ctx, cell, out)


def make_context(root, args, cell, devs, peaks) -> SimpleNamespace:
    return SimpleNamespace(
        root=root, seed=int(args.seed), seconds=float(args.seconds),
        trace=bool(args.trace), control=bool(args.control),
        sweep=args.rate is not None, name=args.workload,
        config=cell["config"], traffic=cell["traffic"],
        reference=cell["reference"], devices=devs,
        peaks=peaks, t_start=T_START, compiles=CompileCounter(),
        spans=Spans(), tracer=Tracer(root, args.workload),
        log=lambda msg: print(f"[bench {args.workload}] {msg}",
                              file=sys.stderr, flush=True))


def read_layer_metrics(ctx, cell, data: Dict) -> Dict[str, Dict]:
    data = dict(data, peaks=ctx.peaks, config=ctx.config,
                kernel=lambda k: load_module(ctx.root / "bench" / "kernels"
                                             / f"{k}.py"))
    out = {}
    for m in cell["per_layer"]:
        v = load_module(cell["readers"][m["name"]]).read(data)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def emit(ctx, cell, out: Dict) -> int:
    """Print the result line. ``out`` holds ``e2e`` (name -> value),
    ``layer_data`` (what the readers read), ``checks`` (name -> (value,
    limit)), ``attempted``, ``failed``, ``memory_peak_bytes`` and, traced,
    ``busy_s``, ``window_s`` and ``breakdown``."""
    checks = out["checks"]
    correct = all(math.isfinite(v) and v <= lim for v, lim in checks.values())
    dev = ctx.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(ctx.devices),
              "memory_peak_bytes": int(out["memory_peak_bytes"])}
    if ctx.trace:
        device.update(busy_s=out["busy_s"], window_s=out["window_s"])
        metrics = read_layer_metrics(ctx, cell, out["layer_data"])
    else:
        metrics = {}
        for m in cell["end_to_end"]:
            v = out["e2e"].get(m["name"])
            if v is None or not math.isfinite(v):
                raise RuntimeError(f"metric {m['name']} not measured: {v}")
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": device}
    if ctx.trace and out.get("breakdown"):
        line["breakdown"] = out["breakdown"]
    for k, v in out.get("extra", {}).items():
        line[k] = v
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0
