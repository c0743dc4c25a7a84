"""Unified decoder LM: dense / MoE / SSM / hybrid stacks from one ModelConfig.

Layers execute as ``lax.scan`` over parameter-stacked *blocks* (see
repro.models.config). Three execution modes share one code path:

  train   — full forward, returns (logits, aux_loss); remat per block.
  prefill — full forward, additionally returns per-layer caches
            (KV rolling/dense buffers, SSM/RWKV states).
  decode  — one token step against the cache.

Params are nested dicts; ``init_shape`` produces the ShapeDtypeStruct tree via
``jax.eval_shape`` so 100B+ configs can be lowered without allocation.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import mamba as M
from repro.models import mlp as F
from repro.models import rwkv as R
from repro.models.common import (
    apply_norm,
    cdtype,
    embed_init,
    dense_init,
    init_norm,
    pdtype,
    softcap,
)
from repro.models.config import LayerSpec, ModelConfig, block_structure
from repro.parallel import logical


class DecoderLM:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.block_size, self.n_blocks, self.specs = block_structure(cfg)

    # ------------------------------------------------------------------ init

    def _init_layer(self, key, spec: LayerSpec):
        cfg = self.cfg
        dt = pdtype(cfg)
        ks = jax.random.split(key, 4)
        lp: Dict[str, Any] = {"norm1": init_norm(cfg), "norm2": init_norm(cfg)}
        if spec.mixer == "attn":
            lp["attn"] = A.init_attention(ks[0], cfg, dt)
        elif spec.mixer == "mamba":
            lp["mamba"] = M.init_mamba(ks[0], cfg, dt)
        elif spec.mixer == "rwkv":
            lp["tm"] = R.init_rwkv_tm(ks[0], cfg, dt)
        else:
            raise ValueError(spec.mixer)
        if spec.mixer == "rwkv":
            lp["cm"] = R.init_rwkv_cm(ks[1], cfg, dt)
        elif spec.is_moe:
            lp["moe"] = F.init_moe(ks[1], cfg, dt)
        else:
            lp["mlp"] = F.init_mlp(ks[1], cfg, dt)
        if cfg.post_norm:
            lp["norm1_post"] = init_norm(cfg)
            lp["norm2_post"] = init_norm(cfg)
        return lp

    def init(self, key):
        cfg = self.cfg
        kE, kH, kB = jax.random.split(key, 3)
        params: Dict[str, Any] = {}
        if cfg.embed_inputs:
            params["embed"] = embed_init(kE, (cfg.vocab_size, cfg.d_model), pdtype(cfg))
        if not (cfg.tie_embeddings and cfg.embed_inputs):
            params["lm_head"] = dense_init(kH, (cfg.d_model, cfg.vocab_size), dtype=pdtype(cfg))
        if "rwkv" in cfg.mixer_pattern:
            params["ln0"] = init_norm(cfg)
        params["final_norm"] = init_norm(cfg)
        bkeys = jax.random.split(kB, self.n_blocks * self.block_size)
        # one vmapped layer init per block position (keys i*block_size + j):
        # the same values as initializing the n_blocks layers one by one,
        # in a program that does not grow with depth
        params["blocks"] = [
            jax.vmap(lambda k, spec=spec: self._init_layer(k, spec))(
                bkeys[j::self.block_size])
            for j, spec in enumerate(self.specs)]
        return params

    def init_shape(self):
        return jax.eval_shape(self.init, jax.random.PRNGKey(0))

    def param_count(self) -> int:
        shapes = self.init_shape()
        return sum(int(math.prod(l.shape)) for l in jax.tree.leaves(shapes))

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only routed experts count)."""
        cfg = self.cfg
        total = self.param_count()
        if cfg.num_experts == 0:
            return total
        shapes = self.init_shape()
        expert_leaves = 0
        for j, spec in enumerate(self.specs):
            if spec.is_moe:
                blk = shapes["blocks"][j]["moe"]
                for name in ("w_gate", "w_up", "w_out"):
                    expert_leaves += int(math.prod(blk[name].shape))
        active_frac = cfg.experts_per_token / cfg.num_experts
        return int(total - expert_leaves * (1.0 - active_frac))

    # ----------------------------------------------------------------- layers

    def _apply_layer(self, lp, x, spec: LayerSpec, *, positions, mode,
                     cache=None, pos=None, max_len=None, true_len=None,
                     pages=None):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        new_cache = None
        h = apply_norm(lp["norm1"], x, cfg)
        rwkv_parts = {}
        if spec.mixer != "attn" and (mode == "decode_paged" or true_len is not None):
            raise NotImplementedError(
                f"paged decode / bucketed (true_len) prefill support attention "
                f"layers only, got mixer={spec.mixer!r} — use the dense path")
        if spec.mixer == "attn":
            if mode == "train":
                y = A.attn_train(lp["attn"], h, cfg, spec, positions)
            elif mode == "prefill":
                y, new_cache = A.attn_prefill(lp["attn"], h, cfg, spec, positions,
                                              max_len=max_len, true_len=true_len)
            elif mode == "decode_paged":
                y, new_cache = A.attn_decode_paged(lp["attn"], h, cache, cfg,
                                                   spec, pos, pages)
            else:
                y, new_cache = A.attn_decode(lp["attn"], h, cache, cfg, spec, pos)
        elif spec.mixer == "mamba":
            if mode == "train":
                y = M.mamba_train(lp["mamba"], h, cfg)
            elif mode == "prefill":
                y, new_cache = M.mamba_prefill(lp["mamba"], h, cfg)
            else:
                y, new_cache = M.mamba_decode(lp["mamba"], h, cache, cfg)
        else:  # rwkv
            if mode == "train":
                y, _, _ = R.rwkv_time_mix(lp["tm"], h, cfg)
            elif mode == "prefill":
                y, sh, s = R.rwkv_time_mix(lp["tm"], h, cfg)
                rwkv_parts.update(shift_tm=sh, wkv=s)
            else:
                y, sh, s = R.rwkv_time_mix(
                    lp["tm"], h, cfg, cache["shift_tm"], cache["wkv"]
                )
                rwkv_parts.update(shift_tm=sh, wkv=s)
        if cfg.post_norm:
            y = apply_norm(lp["norm1_post"], y, cfg)
        x = x + y

        h = apply_norm(lp["norm2"], x, cfg)
        if spec.mixer == "rwkv":
            if mode == "train":
                y, _ = R.rwkv_channel_mix(lp["cm"], h, cfg)
            else:
                cm_state = None if mode == "prefill" else cache["shift_cm"]
                y, sh_cm = R.rwkv_channel_mix(lp["cm"], h, cfg, cm_state)
                rwkv_parts["shift_cm"] = sh_cm
                new_cache = rwkv_parts
        elif spec.is_moe:
            y, aux = F.apply_moe(lp["moe"], h, cfg)
        else:
            y = F.apply_mlp(lp["mlp"], h, cfg)
        if cfg.post_norm:
            y = apply_norm(lp["norm2_post"], y, cfg)
        x = x + y
        return x, aux, new_cache

    # ----------------------------------------------------------------- stack

    def _block_body(self, x, block_params, block_cache, *, positions, mode, pos,
                    max_len=None, true_len=None, pages=None):
        aux_t = jnp.zeros((), jnp.float32)
        new_entries = []
        for j, spec in enumerate(self.specs):
            entry = None if block_cache is None else block_cache[j]
            x, aux, nc = self._apply_layer(
                block_params[j], x, spec, positions=positions, mode=mode,
                cache=entry, pos=pos, max_len=max_len, true_len=true_len,
                pages=pages,
            )
            aux_t = aux_t + aux
            new_entries.append(nc)
        return x, aux_t, new_entries

    def _stack(self, params, x, positions, mode, cache=None, pos=None,
               max_len=None, true_len=None, pages=None):
        cfg = self.cfg
        if mode == "train":
            def body(x, bp):
                xo, aux, _ = self._block_body(
                    x, bp, None, positions=positions, mode="train", pos=None)
                return xo, aux

            if cfg.remat == "full":
                body = jax.checkpoint(body)
            elif cfg.remat == "dots":
                body = jax.checkpoint(
                    body, policy=jax.checkpoint_policies.checkpoint_dots)

            def sb(carry, bp):
                xc, auxc = carry
                xo, aux = body(xc, bp)
                return (xo, auxc + aux), None

            (x, aux), _ = jax.lax.scan(sb, (x, jnp.zeros((), jnp.float32)),
                                       params["blocks"])
            return x, aux, None
        if mode == "prefill":
            def sb(xc, bp):
                xo, _, nc = self._block_body(
                    xc, bp, None, positions=positions, mode="prefill", pos=None,
                    max_len=max_len, true_len=true_len)
                return xo, nc

            x, caches = jax.lax.scan(sb, x, params["blocks"])
            return x, jnp.zeros((), jnp.float32), caches
        # decode / decode_paged (pos is a scalar for decode, a (B,) vector of
        # per-slot positions for decode_paged; pages threads the page table).
        # Named scopes: what the scan does itself (slicing each block's
        # params and cache out of the stack, writing the new cache back)
        # falls under ``layer_scan`` alone, the block's own work under
        # ``layer_scan/.../layer_body``
        def sb(xc, inp):
            bp, bc = inp
            with jax.named_scope("layer_body"):
                xo, _, nc = self._block_body(
                    xc, bp, bc, positions=positions, mode=mode, pos=pos,
                    pages=pages)
            return xo, nc

        with jax.named_scope("layer_scan"):
            x, caches = jax.lax.scan(sb, x, (params["blocks"], cache))
        return x, jnp.zeros((), jnp.float32), caches

    # ------------------------------------------------------------- embeddings

    def _embed_in(self, params, tokens=None, embeds=None, prefix_embeds=None):
        cfg = self.cfg
        dt = cdtype(cfg)
        if cfg.embed_inputs:
            x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        else:
            x = embeds.astype(dt)
        if prefix_embeds is not None:
            x = jnp.concatenate([prefix_embeds.astype(dt), x], axis=1)
        if cfg.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
        if "ln0" in params:
            x = apply_norm(params["ln0"], x, cfg)
        return logical(x, "batch", "act_seq", None)

    def _unembed(self, params, x):
        cfg = self.cfg
        x = apply_norm(params["final_norm"], x, cfg)
        if cfg.tie_embeddings and cfg.embed_inputs:
            logits = x @ params["embed"].astype(x.dtype).T
        else:
            logits = x @ params["lm_head"].astype(x.dtype)
        logits = softcap(logits, cfg.final_softcap)
        return logical(logits, "batch", "act_seq", "vocab")

    # ----------------------------------------------------------------- public

    def forward(self, params, *, tokens=None, embeds=None, prefix_embeds=None):
        """Full training/scoring forward. Returns (logits, aux_loss)."""
        x = self._embed_in(params, tokens, embeds, prefix_embeds)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, aux, _ = self._stack(params, x, positions, "train")
        return self._unembed(params, x), aux

    def loss(self, params, batch) -> Tuple[jax.Array, Dict[str, jax.Array]]:
        """Next-token CE (+ MoE aux). Batch layout per family:

        lm:    {"tokens": (B,S)}
        audio: {"embeds": (B,S,d), "labels": (B,S)}  (labels pre-aligned)
        vlm:   {"prefix_embeds": (B,P,d), "tokens": (B,S_text)}
        """
        cfg = self.cfg
        if cfg.family == "audio":
            logits, aux = self.forward(params, embeds=batch["embeds"])
            labels = batch["labels"]
            mask = jnp.ones(labels.shape, jnp.float32)
        elif cfg.family == "vlm":
            logits, aux = self.forward(
                params, tokens=batch["tokens"], prefix_embeds=batch["prefix_embeds"])
            P = batch["prefix_embeds"].shape[1]
            full = jnp.concatenate(
                [jnp.zeros((batch["tokens"].shape[0], P), jnp.int32), batch["tokens"]],
                axis=1)
            labels = jnp.roll(full, -1, axis=1)
            S = full.shape[1]
            pos_idx = jnp.arange(S)
            mask = ((pos_idx >= P - 1) & (pos_idx < S - 1)).astype(jnp.float32)
            mask = jnp.broadcast_to(mask[None], labels.shape)
        else:
            tokens = batch["tokens"]
            logits, aux = self.forward(params, tokens=tokens)
            labels = jnp.roll(tokens, -1, axis=1)
            S = tokens.shape[1]
            mask = jnp.broadcast_to(
                (jnp.arange(S) < S - 1).astype(jnp.float32)[None], labels.shape)

        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        ce = -jnp.take_along_axis(lp, labels[..., None], axis=-1)[..., 0]
        denom = jnp.maximum(mask.sum(), 1.0)
        ce = (ce * mask).sum() / denom
        loss = ce + cfg.router_aux_coef * aux
        return loss, {"loss": loss, "ce": ce, "aux": aux}

    # cache ------------------------------------------------------------------

    def init_cache(self, batch: int, max_len: int):
        cfg = self.cfg
        caches = []
        for spec in self.specs:
            if spec.mixer == "attn":
                entry = A.init_cache_entry(cfg, spec, batch, max_len)
            elif spec.mixer == "mamba":
                entry = M.init_mamba_cache(cfg, batch)
            else:
                entry = R.init_rwkv_cache(cfg, batch)
            caches.append(
                jax.tree.map(lambda l: jnp.broadcast_to(l[None], (self.n_blocks,) + l.shape), entry)
            )
        return caches

    def cache_shape(self, batch: int, max_len: int):
        return jax.eval_shape(lambda: self.init_cache(batch, max_len))

    def init_paged_cache(self, n_phys_blocks: int, block_size: int,
                         quant: Optional[str] = None):
        """Per-layer paged KV pools (attention-only stacks — the paged data
        plane covers KV caches; SSM/RWKV state is not positional and stays on
        the dense slot path). Block ids are owned by
        ``repro.runtime.paging.PageAllocator``."""
        cfg = self.cfg
        caches = []
        for spec in self.specs:
            if spec.mixer != "attn":
                raise NotImplementedError(
                    f"paged KV cache supports attention layers only, got "
                    f"mixer={spec.mixer!r} (use init_cache / the dense layout)")
            entry = A.init_paged_entry(cfg, spec, n_phys_blocks, block_size,
                                       quant=quant)
            caches.append(
                jax.tree.map(lambda l: jnp.broadcast_to(l[None], (self.n_blocks,) + l.shape), entry)
            )
        return caches

    def prefill(self, params, *, tokens=None, embeds=None, prefix_embeds=None,
                max_len=None, true_len=None):
        """Returns (last_token_logits (B,V), cache). ``max_len`` sizes the KV
        cache for subsequent decode (defaults to the prefill length).

        ``true_len`` (traced scalar int32) marks a right-padded bucketed
        prompt: logits come from position ``true_len - 1`` and cache slots at
        pad positions carry pos=-1 (masked) — one compiled program per bucket
        length serves every true length inside it."""
        x = self._embed_in(params, tokens, embeds, prefix_embeds)
        positions = jnp.arange(x.shape[1], dtype=jnp.int32)
        x, _, caches = self._stack(params, x, positions, "prefill",
                                   max_len=max_len, true_len=true_len)
        if true_len is None:
            last = x[:, -1:, :]
        else:
            last = jax.lax.dynamic_slice_in_dim(x, true_len - 1, 1, axis=1)
        logits = self._unembed(params, last)
        return logits[:, 0, :], caches

    def decode_step(self, params, cache, *, tokens=None, embeds=None, pos=None):
        """One decode step. tokens: (B,1) (or embeds (B,1,d)); pos: scalar int32.

        Returns (logits (B,V), new_cache)."""
        x = self._embed_in(params, tokens, embeds, None)
        x, _, caches = self._stack(params, x, None, "decode", cache=cache, pos=pos)
        logits = self._unembed(params, x)
        return logits[:, 0, :], caches

    def decode_step_paged(self, params, pools, *, tokens=None, pos_vec=None,
                          pages=None):
        """One slot-batched decode step against paged KV pools.

        tokens: (B,1); pos_vec: (B,) int32 per-slot absolute positions;
        pages: (B,P) int32 page-table rows (all traced — the compiled program
        is independent of which physical blocks a slot owns). Returns
        (logits (B,V), pools')."""
        x = self._embed_in(params, tokens, None, None)
        x, _, pools = self._stack(params, x, None, "decode_paged", cache=pools,
                                  pos=pos_vec, pages=pages)
        logits = self._unembed(params, x)
        return logits[:, 0, :], pools


def build_model(cfg: ModelConfig) -> DecoderLM:
    return DecoderLM(cfg)
