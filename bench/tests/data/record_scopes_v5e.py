"""Records the fixture of ``bench/tests/test_bench_scopes.py`` on a TPU:
``scopes_v5e.xplane.pb``, a profiler trace of three calls of a small
jitted program that holds named scopes around and inside a ``lax.scan``,
each call inside the engine's host spans, and ``scopes_v5e.hlo.txt``, the
HLO text of the executable that ran them (source locations left out).

    python bench/tests/data/record_scopes_v5e.py <out_dir>
"""

from __future__ import annotations

import pathlib
import re
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[3]


def decode_step(w, x, cache):
    """A stand-in for the decode step: each of four layers writes its input
    into a carried cache (``kv_write``) and multiplies it by its weights."""
    import jax
    import jax.numpy as jnp

    def body(carry, wi):
        h, cache, i = carry
        with jax.named_scope("layer_body"):
            with jax.named_scope("kv_write"):
                cache = jax.lax.dynamic_update_slice(cache, h[None], (i, 0, 0))
            h = jnp.tanh(h @ wi)
        return (h, cache, i + 1), None

    with jax.named_scope("layer_scan"):
        (h, cache, _), _ = jax.lax.scan(body, (x, cache, 0), w)
    return h.sum(), cache


def hlo_text(name: str) -> str:
    """The HLO text of the live executable ``name``, without the source
    locations (file names and stack frames) it carries."""
    import jax

    (text,) = [m.to_string() for ex in jax.devices()[0].client
               .live_executables() for m in ex.hlo_modules()
               if m.name == name]
    lines = text.splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if ln.startswith(("%", "ENTRY")))
    body = "\n".join([lines[0], ""] + lines[first:]) + "\n"
    return re.sub(r",? ?(stack_frame_id|source_line|source_end_line|"
                  r"source_column|source_end_column)=\d+|,? ?source_file="
                  r'"[^"]*"', "", body)


def main(out: pathlib.Path) -> None:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.obs import span

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record this fixture on a TPU")
    # source files by their base name only, in the trace and the HLO
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    f = jax.jit(decode_step, donate_argnums=2)
    w = jnp.ones((4, 512, 512), jnp.bfloat16) / 512
    x = jnp.ones((512, 512), jnp.bfloat16)
    cache = jnp.zeros((4, 512, 512), jnp.bfloat16)
    y, cache = f(w, x, cache)  # compile outside the trace
    np.asarray(y)
    tmp = out / "trace"
    shutil.rmtree(tmp, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                with span("batcher.step"):
                    with span("batcher.dispatch"):
                        y, cache = f(w, x, cache)
                    with span("batcher.readback"):
                        np.asarray(y)
                    with span("batcher.bookkeep"):
                        time.sleep(0.002)
            with jax.profiler.TraceAnnotation("bench.idle"):
                time.sleep(0.002)
    jax.profiler.stop_trace()
    shutil.copy(sorted(tmp.glob("**/*.xplane.pb"))[-1],
                out / "scopes_v5e.xplane.pb")
    (out / "scopes_v5e.hlo.txt").write_text(hlo_text("jit_decode_step"))
    shutil.rmtree(tmp)


if __name__ == "__main__":
    main(pathlib.Path(sys.argv[1]))
