"""Plain float32 reference of a pre-norm dense decoder (StarCoder2 family),
and the seeded weights that both the system under test and this reference
use.

Written from the published description (arXiv:2402.19173 and the model's
config): token embedding, then per layer LayerNorm -> grouped-query
attention with biases and NeoX split-half rotary embeddings over a causal
sliding window -> residual, LayerNorm -> GeLU(tanh) MLP with biases ->
residual; a final LayerNorm and the tied embedding as the output head.

Nothing here imports the program. The weights are made here, from the
seed, in the layout the program's parameter tree uses; the reference makes
them again itself, leaf by leaf and layer by layer, instead of reading what
the program holds. Every matrix product runs at ``Precision.HIGHEST``
(a float32 product on the TPU is otherwise rounded to bfloat16).

``precision="fp8"`` is the control: the same forward with every matrix
product's inputs rounded to float8 e4m3 (per-row scales for activations,
per-output-column scales for weights), the nearest step below the
configuration's bfloat16.

A replica configuration names its reference (``"reference":
"dense_decoder"``), and ``bench/systems/replica.py`` loads it by path. Every
reference module has the same names, and holds all that depends on the
model's architecture:

- ``WIDTHS``: the keys of the configuration's ``model`` section handed to
  the program's config;
- ``STRUCTURE``: the program-config fields, with their values, that the
  layer equations here assume (a departure is refused before a run);
- ``TINY``: the widths the CPU tests cut the model to;
- ``program_params(seed, m, dtype)``: the program's parameter tree;
- ``reference_logits(seed, m, seqs, wanted, *, precision, lengths,
  n_out)``: float32 logits, ``precision="fp8"`` the control;
- ``served_gaps(ref_logits, tokens)``: each served token's gap below the
  reference's best.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

#: leaves of one layer, in the order their keys are folded from the layer key
LAYER_LEAVES = (
    ("norm1", "scale"), ("norm1", "bias"), ("norm2", "scale"),
    ("norm2", "bias"), ("attn", "wq"), ("attn", "wk"), ("attn", "wv"),
    ("attn", "wo"), ("attn", "bq"), ("attn", "bk"), ("attn", "bv"),
    ("attn", "bo"), ("mlp", "w_in"), ("mlp", "b_in"), ("mlp", "w_out"),
    ("mlp", "b_out"))
TOP_LEAF_ID = {"embed": 0, "final_scale": 1, "final_bias": 2}
#: the layer key of layer ``i`` is ``fold_in(key, LAYER_KEY_BASE + i)``
LAYER_KEY_BASE = 16

FP8_MAX = 448.0  # largest finite float8 e4m3fn

WIDTHS = ("num_layers", "d_model", "num_heads", "num_kv_heads", "head_dim",
          "d_ff", "vocab_size", "window_size", "rope_theta", "norm_eps")
STRUCTURE = {"mlp_type": "gelu", "norm_type": "layernorm", "use_bias": True,
             "tie_embeddings": True, "pos_type": "rope", "attn_softcap": 0.0,
             "final_softcap": 0.0, "post_norm": False, "embed_scale": False,
             "prefix_len": 0, "norm_plus_one": False,
             "attn_pattern": ("local",)}
TINY = dict(num_layers=2, d_model=64, num_heads=4, num_kv_heads=2,
            head_dim=16, d_ff=256, vocab_size=128, window_size=64)


def base_key(seed: int):
    """A threefry key from any non-negative integer seed (64 bits used)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    data = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)
    return jax.random.wrap_key_data(data, impl="threefry2x32")


def _leaf_shape(m: Dict, group: str, name: str):
    d, H, KV, hd, ff = (m["d_model"], m["num_heads"], m["num_kv_heads"],
                        m["head_dim"], m["d_ff"])
    return {"scale": (d,), "bias": (d,), "wq": (d, H * hd),
            "wk": (d, KV * hd), "wv": (d, KV * hd), "wo": (H * hd, d),
            "bq": (H * hd,), "bk": (KV * hd,), "bv": (KV * hd,), "bo": (d,),
            "w_in": (d, ff), "b_in": (ff,), "w_out": (ff, d),
            "b_out": (d,)}[name]


def _draw(key, shape, kind: str):
    """Weights ~ N(0, 1/fan_in); biases ~ N(0, 0.02^2); norm scales
    ~ 1 + N(0, 0.1^2). Nonzero biases and uneven scales keep every term of
    the layer equations in play."""
    import jax

    z = jax.random.normal(key, shape, np.float32)
    if kind == "weight":
        return z * np.float32(shape[0] ** -0.5)
    if kind == "scale":
        return 1.0 + np.float32(0.1) * z
    return np.float32(0.02) * z


def layer_params(key, layer, m: Dict, dtype):
    """Layer ``layer``'s leaves as a nested dict, drawn in float32 and
    rounded to ``dtype``. ``layer`` may be traced (vmapped)."""
    import jax

    lk = jax.random.fold_in(key, LAYER_KEY_BASE + layer)
    out: Dict[str, Dict] = {}
    for j, (group, name) in enumerate(LAYER_LEAVES):
        kind = ("weight" if name.startswith("w") else
                "scale" if name == "scale" else "bias")
        out.setdefault(group, {})[name] = _draw(
            jax.random.fold_in(lk, j), _leaf_shape(m, group, name),
            kind).astype(dtype)
    return out


def top_params(key, m: Dict, dtype):
    import jax

    V, d = m["vocab_size"], m["d_model"]
    emb = jax.random.normal(jax.random.fold_in(key, TOP_LEAF_ID["embed"]),
                            (V, d), np.float32) * np.float32(d ** -0.5)
    return {
        "embed": emb.astype(dtype),
        "final_norm": {
            "scale": _draw(jax.random.fold_in(key, TOP_LEAF_ID["final_scale"]),
                           (d,), "scale").astype(dtype),
            "bias": _draw(jax.random.fold_in(key, TOP_LEAF_ID["final_bias"]),
                          (d,), "bias").astype(dtype)},
    }


def program_params(seed: int, m: Dict, dtype):
    """The whole parameter tree, in the program's layout (layers stacked on
    a leading axis, one block position), made on the device in one jitted
    call from ``seed``."""
    import jax
    import jax.numpy as jnp

    dt = jnp.dtype(dtype)

    @jax.jit
    def make(key):
        p = top_params(key, m, dt)
        p["blocks"] = [jax.vmap(lambda i: layer_params(key, i, m, dt))(
            jnp.arange(m["num_layers"]))]
        return p

    return make(base_key(seed))


# ------------------------------------------------------------------ forward


def _q8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    import jax.numpy as jnp

    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / FP8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, fp8: bool):
    """``a @ w`` in float32 at HIGHEST; with ``fp8`` both inputs are first
    rounded to float8 (a per row, w per output column)."""
    import jax
    import jax.numpy as jnp

    if fp8:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _layernorm(x, p, eps):
    import jax.numpy as jnp

    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _rope(x, pos, theta):
    """NeoX split-half rotation. x: (T, heads, hd); pos: (T,)."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], -1)


def _gelu_tanh(x):
    import jax.numpy as jnp

    return 0.5 * x * (1.0 + jnp.tanh(np.float32(np.sqrt(2.0 / np.pi))
                                     * (x + 0.044715 * x ** 3)))


def _layer(lp, x, valid_len, m: Dict, fp8: bool, q_block: int):
    """One layer over one right-padded sequence x: (T, d). Queries are
    taken ``q_block`` rows at a time so the score matrix stays small."""
    import jax
    import jax.numpy as jnp

    T = x.shape[0]
    H, KV, hd = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    G = H // KV
    pos = jnp.arange(T)
    a = lp["attn"]
    h = _layernorm(x, lp["norm1"], m["norm_eps"])
    q = (_mm(h, a["wq"], fp8) + a["bq"]).reshape(T, H, hd)
    k = (_mm(h, a["wk"], fp8) + a["bk"]).reshape(T, KV, hd)
    v = (_mm(h, a["wv"], fp8) + a["bv"]).reshape(T, KV, hd)
    q, k = _rope(q, pos, m["rope_theta"]), _rope(k, pos, m["rope_theta"])
    k = jnp.repeat(k, G, axis=1)  # (T, H, hd): query head i reads kv i // G
    v = jnp.repeat(v, G, axis=1)
    kt = k.transpose(1, 2, 0)     # (H, hd, T)
    vh = v.transpose(1, 0, 2)     # (H, T, hd)
    if fp8:
        kt, vh = _q8(kt, 1), _q8(vh, 1)
    window = m["window_size"]

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * q_block, q_block, 0)
        qb = qb.transpose(1, 0, 2)  # (H, qb, hd)
        if fp8:
            qb = _q8(qb, -1)
        s = jnp.matmul(qb, kt, precision=jax.lax.Precision.HIGHEST)
        s = s * np.float32(hd ** -0.5)
        qp = i * q_block + jnp.arange(q_block)
        ok = ((pos[None, :] <= qp[:, None])
              & (qp[:, None] - pos[None, :] < window)
              & (pos[None, :] < valid_len))
        # finite: a padded row may see no key at all, and 0 * inf would
        # carry NaN into the valid rows through its values
        s = jnp.where(ok[None], s, np.float32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _q8(p, -1)
        o = jnp.matmul(p, vh, precision=jax.lax.Precision.HIGHEST)
        return o.transpose(1, 0, 2).reshape(q_block, H * hd)

    o = jax.lax.map(block, jnp.arange(T // q_block)).reshape(T, H * hd)
    x = x + _mm(o, a["wo"], fp8) + a["bo"]
    f = lp["mlp"]
    h = _layernorm(x, lp["norm2"], m["norm_eps"])
    h = _gelu_tanh(_mm(h, f["w_in"], fp8) + f["b_in"])
    return x + _mm(h, f["w_out"], fp8) + f["b_out"]


def reference_logits(seed: int, m: Dict, seqs: Sequence[np.ndarray],
                     wanted: Sequence[np.ndarray], *,
                     precision: str = "f32",
                     lengths: Sequence[int] = (1024, 2048, 4096),
                     n_out: int = 512) -> List[np.ndarray]:
    """Float32 logits of each sequence at its ``wanted`` positions.

    Computed layer by layer over all sequences (each layer's weights are
    drawn once, from the seed, in the configuration's dtype and widened to
    float32). Each sequence is right-padded to the shortest of ``lengths``
    that holds it, and each ``wanted`` to ``n_out`` positions, so a few
    compiled programs serve every run. Returns one ``(len(wanted[i]),
    vocab)`` float32 array per sequence."""
    import jax
    import jax.numpy as jnp

    if precision not in ("f32", "fp8"):
        raise ValueError(f"precision must be 'f32' or 'fp8': {precision!r}")
    fp8 = precision == "fp8"
    dt = jnp.dtype(m["dtype"])
    key = base_key(seed)
    lengths = sorted(lengths)
    to32 = lambda t: jax.tree.map(lambda l: l.astype(jnp.float32), t)
    top = to32(jax.jit(lambda k: top_params(k, m, dt))(key))
    make_layer = jax.jit(lambda k, i: to32(layer_params(k, i, m, dt)))
    run_layer = jax.jit(lambda lp, x, n: _layer(
        lp, x, n, m, fp8, min(512, x.shape[0])))
    embed = jax.jit(lambda e, t: e[t])

    xs, lens = [], []
    for s in seqs:
        n = len(s)
        fit = [L for L in lengths if L >= n]
        if not fit or len(s) == 0:
            raise ValueError(f"sequence of {n} tokens fits none of {lengths}")
        toks = np.zeros(fit[0], np.int32)
        toks[:n] = s
        xs.append(embed(top["embed"], jnp.asarray(toks)))
        lens.append(jnp.asarray(n, jnp.int32))
    for i in range(m["num_layers"]):
        lp = make_layer(key, jnp.asarray(i, jnp.int32))
        xs = [run_layer(lp, x, n) for x, n in zip(xs, lens)]
        del lp

    @jax.jit
    def head(top, x, idx):  # weights as arguments, not program constants
        h = _layernorm(x[idx], top["final_norm"], m["norm_eps"])
        return _mm(h, top["embed"].T, fp8)

    out = []
    for x, w in zip(xs, wanted):
        if len(w) > n_out:
            raise ValueError(f"{len(w)} positions wanted, over n_out={n_out}")
        idx = np.zeros(n_out, np.int32)
        idx[:len(w)] = w
        out.append(np.asarray(head(top, x, jnp.asarray(idx)))[:len(w)])
    return out


def served_gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far below the reference's best logit each served token lies."""
    ref_logits = np.asarray(ref_logits, np.float64)
    tokens = np.asarray(tokens)
    return (ref_logits.max(-1)
            - ref_logits[np.arange(len(tokens)), tokens])
