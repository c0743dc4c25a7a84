"""Chip-compiler rehearsal: the Pallas kernels at real widths, and
starcoder2-3b's paged decode step with the kernels on, compiled for a
described TPU v5e (nothing runs; no chip needed).

Interpret mode checks what a kernel computes; only the TPU compiler checks
whether its block shapes tile and its scratch fits VMEM. Every test asserts
the compiled program holds the Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and the workers of a
parallel test run all import this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.kernel import (decode_attention_fwd,
                                                   paged_decode_attention_fwd)
from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.rwkv6_scan.ops import rwkv6_scan
from repro.kernels.ssm_scan.ops import ssm_scan


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described chip, with the persistent compilation cache off: an
    entry compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# starcoder2-3b decode widths: 8 slots, 24 query / 2 kv heads of 128,
# max_len 4096 in 16-token pages
B, H, KV, HD, BS, L = 8, 24, 2, 128, 16, 4096
N_PHYS = 2 + B * (L // BS)


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8])
def test_paged_decode_attention_compiles(one_chip, pool_dtype):
    """The live-page walk: per-slot page counts, manual page DMAs."""
    S = _spec(one_chip)
    pool = S((N_PHYS, KV, BS, HD), pool_dtype)
    args = [S((B, H, HD), jnp.bfloat16), pool, pool,
            S((B, L // BS), jnp.int32), S((B, L)), S((B,), jnp.int32)]
    if pool_dtype == jnp.int8:
        scale = S((N_PHYS, KV, BS, 1))
        _compile(lambda q, k, v, t, b, n, ks, vs: paged_decode_attention_fwd(
            q, k, v, t, b, n_pages=n, k_scale=ks, v_scale=vs),
            *args, scale, scale)
    else:
        _compile(lambda q, k, v, t, b, n: paged_decode_attention_fwd(
            q, k, v, t, b, n_pages=n), *args)


def test_decode_attention_compiles(one_chip):
    S = _spec(one_chip)
    kv = S((B, KV, L, HD), jnp.bfloat16)
    _compile(decode_attention_fwd, S((B, H, HD), jnp.bfloat16), kv, kv, S((L,)))


def test_flash_attention_compiles(one_chip):
    S = _spec(one_chip)
    kv = S((1, KV, 1024, HD), jnp.bfloat16)
    _compile(lambda q, k, v: flash_attention_fwd(q, k, v, causal=True,
                                                 window=4096),
             S((1, H, 1024, HD), jnp.bfloat16), kv, kv)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_rwkv6_scan_compiles(one_chip, direction):
    S = _spec(one_chip)
    Hr, hd, Sq = 40, 64, 256  # rwkv6-3b: 40 heads of 64
    seq = S((1, Hr, Sq, hd))
    args = (seq, seq, seq, seq, S((Hr, hd)), S((1, Hr, hd, hd)))

    def fwd(*a):
        return rwkv6_scan(*a, interpret=False)

    if direction == "fwd":
        _compile(fwd, *args)
    else:
        _compile(jax.grad(lambda *a: fwd(*a)[0].sum(), argnums=(0, 4)), *args)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_ssm_scan_compiles(one_chip, direction):
    S = _spec(one_chip)
    Di, N, Sq = 8192, 16, 256
    xd, bn = S((1, Sq, Di)), S((1, Sq, N))
    args = (xd, xd, S((Di, N)), bn, bn, S((Di,)), S((1, Di, N)))

    def fwd(*a):
        return ssm_scan(*a, interpret=False)

    if direction == "fwd":
        _compile(fwd, *args)
    else:
        _compile(jax.grad(lambda *a: fwd(*a)[0].sum(), argnums=(0, 5)), *args)


def test_starcoder2_paged_decode_step_compiles(one_chip, monkeypatch):
    """The whole data-plane decode step at published widths, kernels on,
    fits one v5e's 16 GB."""
    from repro.configs import get_config
    from repro.models import build_model

    # the model asks the default backend whether to compile its kernels;
    # here that is the CPU, so answer for the described chip
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = build_model(get_config("starcoder2-3b").replace(use_pallas=True))
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=one_chip),
        tree)
    params = place(model.init_shape())
    pools = place(jax.eval_shape(lambda: model.init_paged_cache(N_PHYS, BS)))
    S = _spec(one_chip)
    compiled = _compile(
        lambda p, c, t, pos, tbl: model.decode_step_paged(
            p, c, tokens=t, pos_vec=pos, pages=tbl),
        params, pools, S((B, 1), jnp.int32), S((B,), jnp.int32),
        S((B, L // BS), jnp.int32))
    mem = compiled.memory_analysis()
    param_bytes = sum(l.size * l.dtype.itemsize
                      for l in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= param_bytes  # weights are arguments
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
