"""Pallas TPU kernels for the compute hot-spots of the served workloads.

Each kernel ships three files:
  kernel.py — pl.pallas_call + explicit BlockSpec VMEM tiling (TPU target)
  ops.py    — jit'd public wrapper (+ custom_vjp where training uses it)
  ref.py    — pure-jnp oracle; tests assert allclose over shape/dtype sweeps

On the TPU the kernels compile through Mosaic; ``tests/test_tpu_compile.py``
compiles them at real widths for a described v5e, and ``chip_smoke.py``
runs them inside a whole decode step on the chip. On the CPU backend they
run in interpret mode (the kernel body runs in Python per block), which is
how the parity tests check them against ``ref.py``. Any other backend is an
error: there is no silent interpret fallback on an accelerator. The model
code's default path is pure-XLA jnp (so the multi-pod dry-run lowers without
Mosaic); ``ModelConfig.use_pallas`` routes the hot ops through these kernels.
"""

from __future__ import annotations


def interpret_mode(interpret=None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins;
    otherwise compiled on the TPU, interpreted on the CPU, and an error on
    any other backend."""
    if interpret is not None:
        return bool(interpret)
    import jax

    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels compile for the TPU and interpret on the CPU; "
        f"backend {backend!r} is neither")
