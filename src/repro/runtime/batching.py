"""Continuous-batching decode engine — the per-replica serving substrate.

What a transient inference replica actually runs: a fixed-slot decode engine
(vLLM-style continuous batching adapted to TPU's static shapes):

  * ``max_slots`` concurrent sequences share one jitted decode step over a
    slot-batched KV cache (B = max_slots, padded); finished sequences free
    their slot immediately and a queued request takes it on the next step —
    no batch-drain barrier;
  * admission runs prefill for the incoming request into the freed slot
    (per-slot cache insertion via the model's prefill + slot scatter);
  * static shapes: one compiled decode step + one compiled prefill per
    prompt-length bucket (power-of-2 multiples of ``prompt_bucket``, clamped
    to ``max_len``; the true length rides in as a traced scalar) —
    TPU-friendly (no dynamic shapes ever);
  * the engine reports slot occupancy to the CloudCoaster controller — it is
    the "server" of the paper's model, and its queue is the queueing delay
    the paper measures.

Two KV layouts share the engine (``kv_layout``):

  dense — every slot owns a padded ``max_len`` cache (batch = max_slots,
    stacked); simple, memory ~ max_slots x max_len regardless of demand.
  paged — one shared pool of ``kv_block_size``-token blocks plus a
    ``repro.runtime.paging.PageAllocator`` page table. The slot<->page
    relationship: slot ``b``'s logical cache slot ``s`` (the same
    ``s = pos % L`` rolling index as the dense cache) lives at physical
    block ``table[b, s // kv_block_size]``, offset ``s % kv_block_size``;
    a request reserves only ``ceil(min(plen + max_new, max_len) /
    kv_block_size)`` pages at admit time (loud ``PagedCacheOOM``, never a
    mid-decode failure), so short sequences stop paying worst-case memory
    and one replica sustains strictly more slots at equal pool bytes
    (benchmarks/decode_scale.py gates the ratio). ``kv_quant="int8"``
    additionally stores pooled K/V int8 with rowwise f32 scales
    (~3.6x smaller at head_dim=32). Gathering a slot's pages reproduces its
    dense cache bit-for-bit, so both layouts generate token-identical
    streams (tests/test_paging.py).

Exercised end-to-end with a real reduced model in tests/test_batching.py,
tests/test_paging.py and examples/serve_bursty.py (engine mode).
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import TYPE_CHECKING, Deque, Dict, List, Optional, Tuple

import numpy as np

if TYPE_CHECKING:  # jax + model imports stay lazy: SlotState is also the
    from repro.models.decoder import DecoderLM  # serving fleet's (numpy-only)
    # slot substrate, and the DES-only multiprocess workers import it


class SlotState:
    """Fixed-capacity decode-slot bookkeeping — the continuous-batching
    substrate shared by :class:`ContinuousBatcher` (real-model decode) and
    the serving fleet's replicas (``repro.runtime.serving``).

    Admit-on-free-slot semantics: a finished occupant frees its slot
    immediately and the lowest free slot takes the next admission — no
    batch-drain barrier. Occupants are opaque to this class (the batcher
    stores ``GenRequest``; the fleet stores its per-slot decode record).
    """

    __slots__ = ("max_slots", "_occupants")

    def __init__(self, max_slots: int):
        if max_slots < 1:
            raise ValueError(f"max_slots must be >= 1, got {max_slots}")
        self.max_slots = int(max_slots)
        self._occupants: List[Optional[object]] = [None] * self.max_slots

    @property
    def n_active(self) -> int:
        return sum(o is not None for o in self._occupants)

    @property
    def n_free(self) -> int:
        return self.max_slots - self.n_active

    @property
    def occupancy(self) -> float:
        return self.n_active / self.max_slots

    def get(self, slot: int):
        return self._occupants[slot]

    def free_slot(self) -> Optional[int]:
        """Lowest free slot index, or None when full."""
        for i, o in enumerate(self._occupants):
            if o is None:
                return i
        return None

    def place(self, slot: int, item) -> None:
        """Admit ``item`` into a specific (free) slot."""
        if self._occupants[slot] is not None:
            raise RuntimeError(f"slot {slot} is occupied")
        self._occupants[slot] = item

    def admit(self, item) -> int:
        """Admit ``item`` into the lowest free slot; returns the slot."""
        slot = self.free_slot()
        if slot is None:
            raise RuntimeError("no free slot")
        self._occupants[slot] = item
        return slot

    def release(self, slot: int):
        """Free a slot; returns the occupant that held it."""
        item = self._occupants[slot]
        if item is None:
            raise RuntimeError(f"slot {slot} is already free")
        self._occupants[slot] = None
        return item

    def clear(self) -> None:
        self._occupants = [None] * self.max_slots

    def items(self) -> List[Tuple[int, object]]:
        """Snapshot of ``(slot, occupant)`` pairs — safe to admit/release
        while iterating (revocation and finish paths mutate mid-scan)."""
        return [(i, o) for i, o in enumerate(self._occupants) if o is not None]

    def occupants(self) -> List[object]:
        return [o for o in self._occupants if o is not None]


@dataclass
class GenRequest:
    rid: int
    prompt: np.ndarray  # (P,) int32
    max_new: int
    arrival: int = 0
    # engine-filled:
    start_step: Optional[int] = None
    finish_step: Optional[int] = None
    tokens: List[int] = field(default_factory=list)

    @property
    def wait(self) -> Optional[int]:
        return None if self.start_step is None else self.start_step - self.arrival


class ContinuousBatcher:
    """Fixed-slot continuous-batching engine over a real decoder model.

    ``kv_layout="dense"`` stacks one padded ``max_len`` cache per slot;
    ``kv_layout="paged"`` admits against a shared block pool through a
    :class:`~repro.runtime.paging.PageAllocator` (see the module docstring
    for the slot<->page contract). ``kv_blocks`` sets the paged pool's
    allocatable block budget (default: full dense capacity,
    ``max_slots * max_len / kv_block_size``); shrinking it trades head-of-line
    admission waits for memory, never correctness. Both layouts share the
    bucketed compiled prefill: one jit entry per power-of-2 bucket
    (``obs.metrics`` counter ``batcher.prefill_compiles`` counts them),
    with an exact-length fallback for stacks the padded path cannot serve
    (SSM/RWKV recurrences consume pad tokens; a bidirectional prefix attends
    them) — the fallback is still cached per length, just retrace-prone.
    """

    def __init__(self, model: "DecoderLM", params, *, max_slots: int = 4,
                 max_len: int = 128, prompt_bucket: int = 16,
                 kv_layout: str = "dense", kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 kv_quant: Optional[str] = None):
        import jax
        import jax.numpy as jnp

        from repro.runtime.paging import RESERVED_BLOCKS, PageAllocator

        if kv_layout not in ("dense", "paged"):
            raise ValueError(f"kv_layout must be 'dense' or 'paged', got {kv_layout!r}")
        if kv_quant not in (None, "int8"):
            raise ValueError(f"kv_quant must be None or 'int8', got {kv_quant!r}")
        if kv_quant is not None and kv_layout != "paged":
            raise ValueError("kv_quant requires kv_layout='paged'")
        self.model = model
        self.params = params
        self.max_slots = max_slots
        self.max_len = max_len
        self.bucket = prompt_bucket
        self.kv_layout = kv_layout
        self.kv_block_size = kv_block_size
        cfg = model.cfg
        # padded-bucket prefill needs pure-attention stacks without a
        # bidirectional prefix (see class docstring)
        self._bucketed = (cfg.prefix_len == 0
                          and all(s.mixer == "attn" for s in model.specs))

        self.pos = np.zeros(max_slots, np.int64)  # next absolute position
        self.remaining = np.zeros(max_slots, np.int64)
        self.slots = SlotState(max_slots)  # occupants: GenRequest
        self.last_tok = jnp.zeros((max_slots, 1), jnp.int32)
        self.queue: Deque[GenRequest] = deque()
        self.step_count = 0
        self.last_logits = None  # (max_slots, V) of the latest decode step
        self._prefills: Dict[int, callable] = {}

        if kv_layout == "paged":
            bs = kv_block_size
            if max_len % bs != 0:
                raise ValueError(
                    f"max_len={max_len} must be a multiple of kv_block_size={bs}")
            from repro.models.attention import cache_len_for
            for spec in model.specs:
                L = cache_len_for(cfg, spec, max_len)
                if L % bs != 0:
                    raise ValueError(
                        f"cache length {L} (attn_type={spec.attn_type!r}, "
                        f"window={cfg.window_size}) must be a multiple of "
                        f"kv_block_size={bs}")
            self.pages_per_slot = max_len // bs
            n_alloc = (max_slots * self.pages_per_slot if kv_blocks is None
                       else kv_blocks)
            self.allocator = PageAllocator(
                n_alloc + RESERVED_BLOCKS, bs, max_slots, self.pages_per_slot)
            # per-layer pools; block ids are shared across layers via the
            # one page table (local layers use only their leading pages)
            self.pools = model.init_paged_cache(
                self.allocator.n_blocks, bs, quant=kv_quant)

            def decode_paged(params, pools, toks, pos_vec, table):
                return self.model.decode_step_paged(
                    params, pools, tokens=toks, pos_vec=pos_vec, pages=table)

            # params ride in as an argument: a closure would embed the
            # weights in the program as constants; the pools are donated
            # (each step replaces them)
            self._decode = jax.jit(decode_paged, donate_argnums=1)
        else:
            # dense: each slot carries its own single-sequence cache (batch=1)
            # stacked on a leading slot axis; the decode step vmaps the
            # single-sequence decoder over slots so per-slot positions are
            # exact.
            one_slot = model.init_cache(1, max_len)
            self.cache_slots = jax.tree.map(
                lambda l: jnp.stack([l] * max_slots), one_slot)

            def decode_slotwise(params, cache_slots, toks, pos_vec):
                def one(cache_slot, tok, pos):
                    logits, new_cache = self.model.decode_step(
                        params, cache_slot, tokens=tok[None], pos=pos)
                    return logits[0], new_cache

                return jax.vmap(one, in_axes=(0, 0, 0))(cache_slots, toks, pos_vec)

            self._decode = jax.jit(decode_slotwise, donate_argnums=1)

    # ---------------------------------------------------------------- intake

    def _pages_for(self, req: GenRequest) -> int:
        from repro.runtime.paging import pages_needed

        return pages_needed(len(req.prompt), req.max_new, self.max_len,
                            self.kv_block_size)

    def submit(self, req: GenRequest):
        """Queue a request. Rejects loudly (static-shape rules: admission
        must never truncate) when the prompt cannot leave room for a single
        generated token, or — paged layout — when the request could never
        fit the block pool even when idle."""
        plen = len(req.prompt)
        if plen < 1 or plen > self.max_len - 1:
            raise ValueError(
                f"prompt length {plen} not in [1, max_len-1={self.max_len - 1}]")
        if self.kv_layout == "paged":
            from repro.runtime.paging import PagedCacheOOM

            need = self._pages_for(req)
            if not self.allocator.fits_ever(need):
                raise PagedCacheOOM(
                    f"request rid={req.rid} needs {need} pages; pool has "
                    f"{self.allocator.n_allocatable} total")
        self.queue.append(req)

    def _bucket_for(self, plen: int) -> int:
        b = self.bucket
        while b < plen:
            b *= 2
        return min(b, self.max_len)

    def _prefill_fn(self, bucket: int):
        import jax

        from repro.obs.metrics import REGISTRY

        if bucket not in self._prefills:
            REGISTRY.counter("batcher.prefill_compiles").inc()
            if self._bucketed:
                def prefill(params, toks, true_len):
                    return self.model.prefill(params, tokens=toks,
                                              max_len=self.max_len,
                                              true_len=true_len)
            else:
                def prefill(params, toks, true_len):
                    del true_len  # exact-length fallback
                    return self.model.prefill(params, tokens=toks,
                                              max_len=self.max_len)

            self._prefills[bucket] = jax.jit(prefill)
        return self._prefills[bucket]

    def prefill(self, prompt: np.ndarray):
        """Run the engine's compiled prefill for one prompt (the bucketed
        program admission uses). Returns ``(logits (1, V), cache)`` with the
        cache sized ``max_len`` for a batch of one."""
        import jax.numpy as jnp

        plen = len(prompt)
        if self._bucketed:
            bucket = self._bucket_for(plen)
            toks = np.zeros(bucket, np.int32)
            toks[:plen] = prompt
        else:
            bucket = plen  # one compiled prefill per distinct length
            toks = np.asarray(prompt, np.int32)
        return self._prefill_fn(bucket)(
            self.params, jnp.asarray(toks)[None], jnp.asarray(plen, jnp.int32))

    def _admit(self, slot: int, req: GenRequest):
        """One request into ``slot``: prefill, pages and scatter, its first
        token on the host, and the slot's state. The ``batcher.admit`` span
        (``rid``) is the request's admission and first-token stamp in a
        profile."""
        import jax
        import jax.numpy as jnp

        from repro.obs.metrics import span

        with span("batcher.admit", rid=req.rid):
            plen = len(req.prompt)
            with span("batcher.prefill"):
                logits, cache1 = self.prefill(req.prompt)
            with span("batcher.scatter"):
                if self.kv_layout == "paged":
                    self._scatter_paged(slot, req, cache1)
                else:
                    # cache1 leaves match a slot cache exactly (batch=1)
                    self.cache_slots = jax.tree.map(
                        lambda all_slots, one: all_slots.at[slot].set(one),
                        self.cache_slots, cache1)
            tok = int(jnp.argmax(logits[0]))
            req.tokens.append(tok)
            req.start_step = self.step_count
            self.last_tok = self.last_tok.at[slot, 0].set(tok)
            self.pos[slot] = plen
            self.remaining[slot] = req.max_new - 1
            self.slots.place(slot, req)

    def _scatter_paged(self, slot: int, req: GenRequest, cache1):
        """Reserve the slot's pages and scatter the prefill cache into the
        pools. All valid prefill content lives within the reserved pages
        (reservation covers every position the request can ever write, and a
        rolling window's slots sit below that bound); unreserved logical
        pages are redirected from the read-only NULL block to the TRASH sink
        so the pool's shared zero tail is never written."""
        import jax.numpy as jnp

        from repro.optim.compress import quantize_int8
        from repro.runtime.paging import NULL_BLOCK, TRASH_BLOCK

        bs = self.kv_block_size
        row = self.allocator.reserve(slot, self._pages_for(req))
        write_row = row.copy()
        write_row[write_row == NULL_BLOCK] = TRASH_BLOCK
        new_pools = []
        for pool, entry in zip(self.pools, cache1):
            nb, _, L = entry["k"].shape[:3]
            KV, hd = entry["k"].shape[3:]
            P = L // bs
            tbl = jnp.asarray(write_row[:P])
            # dense (nb, L, KV, hd) -> kv-head-major pages (nb, P, KV, bs, hd)
            vk = entry["k"][:, 0].reshape(nb, P, bs, KV, hd).swapaxes(2, 3)
            vv = entry["v"][:, 0].reshape(nb, P, bs, KV, hd).swapaxes(2, 3)
            vpos = entry["pos"].reshape(nb, P, bs)
            pool = dict(pool)
            if "k_scale" in pool:
                qk, ks = quantize_int8(vk)
                qv, vs = quantize_int8(vv)
                pool["k"] = pool["k"].at[:, tbl].set(qk)
                pool["v"] = pool["v"].at[:, tbl].set(qv)
                pool["k_scale"] = pool["k_scale"].at[:, tbl].set(ks)
                pool["v_scale"] = pool["v_scale"].at[:, tbl].set(vs)
            else:
                pool["k"] = pool["k"].at[:, tbl].set(vk.astype(pool["k"].dtype))
                pool["v"] = pool["v"].at[:, tbl].set(vv.astype(pool["v"].dtype))
            pool["pos"] = pool["pos"].at[:, tbl].set(vpos)
            new_pools.append(pool)
        self.pools = new_pools

    # ------------------------------------------------------------------ step

    def _can_admit_head(self) -> bool:
        if self.kv_layout != "paged":
            return True
        # head-of-line: FIFO admission waits for pages, never reorders
        return self.allocator.can_reserve(self._pages_for(self.queue[0]))

    def decode_args(self):
        """The decode step's arguments at the engine's current state:
        ``(params, pools, tokens, pos_vec, page_table)`` paged,
        ``(params, cache_slots, tokens, pos_vec)`` dense."""
        import jax.numpy as jnp

        pos = jnp.asarray(self.pos, jnp.int32)
        if self.kv_layout == "paged":
            return (self.params, self.pools, self.last_tok, pos,
                    jnp.asarray(self.allocator.table))
        return self.params, self.cache_slots, self.last_tok, pos

    def lower_decode(self):
        """The jitted decode step lowered at the engine's current state, for
        inspecting the program (its arguments, or which kernels it holds
        once compiled) without running it."""
        return self._decode.lower(*self.decode_args())

    def admit(self) -> None:
        """Prefill queued requests into free slots, head of line first,
        while slots (and, paged, pages) last."""
        while self.queue and self.slots.n_free and self._can_admit_head():
            self._admit(self.slots.free_slot(), self.queue.popleft())

    def step(self) -> int:
        """Admit queued requests into free slots, then decode one token for
        every active slot. Returns number of active slots. The step's
        ``(max_slots, V)`` logits stay in ``last_logits`` (rows of free
        slots are garbage)."""
        import jax.numpy as jnp

        from repro.obs.metrics import span

        with span("batcher.step"):
            self.admit()
            n_active = self.slots.n_active
            if n_active == 0:
                self.step_count += 1
                return 0
            if self.kv_layout == "paged":
                self._count_pages()
            with span("batcher.dispatch"):
                logits, new_cache = self._decode(*self.decode_args())
                if self.kv_layout == "paged":
                    self.pools = new_cache
                else:
                    self.cache_slots = new_cache
                self.last_logits = logits
                toks = jnp.argmax(logits, axis=-1)
            with span("batcher.readback"):  # the host waits on the device
                toks = np.asarray(toks)
            with span("batcher.bookkeep"):
                for slot, req in self.slots.items():
                    req.tokens.append(int(toks[slot]))
                    self.pos[slot] += 1
                    self.remaining[slot] -= 1
                    if (self.remaining[slot] <= 0
                            or self.pos[slot] >= self.max_len - 1):
                        req.finish_step = self.step_count
                        self.slots.release(slot)  # freed for next step
                        # a free slot's position only addresses its
                        # discarded write; 0 keeps its kernel walk to a block
                        self.pos[slot] = 0
                        if self.kv_layout == "paged":
                            self.allocator.free(slot)  # pages back to the pool
                self.last_tok = jnp.asarray(toks[:, None], jnp.int32)
            self.step_count += 1
            return n_active

    def _count_pages(self) -> None:
        """Counters of the paged kernel's walk in this step:
        ``batcher.kv_pages_walked`` adds the live pages of every slot (the
        rule the decode step hands the kernel, on a full-length layer),
        ``batcher.kv_pages_table`` the whole table's pages."""
        from repro.kernels.decode_attention.ops import live_pages
        from repro.obs.metrics import REGISTRY

        P = self.pages_per_slot
        REGISTRY.counter("batcher.kv_pages_walked").inc(
            int(live_pages(self.pos, self.kv_block_size, P).sum()))
        REGISTRY.counter("batcher.kv_pages_table").inc(self.max_slots * P)

    def run(self, until_empty: bool = True, max_steps: int = 10_000):
        """Step the engine. With ``until_empty`` (the default) stepping
        stops once the queue and every slot have drained (or ``max_steps``
        is exhausted) — "empty" means no queued *and* no resident requests,
        so every submitted request has emitted its final token;
        ``until_empty=False`` steps exactly ``max_steps`` times —
        fixed-horizon driving, idle steps included (the serving engine's
        tick-driven mode)."""
        while max_steps > 0 and (not until_empty
                                 or self.queue or self.slots.n_active):
            self.step()
            max_steps -= 1

    def kv_cache_bytes(self) -> int:
        """Resident KV-cache bytes of the current layout (pool arrays for
        paged — page-table bookkeeping is negligible — or the stacked slot
        caches for dense)."""
        import jax

        tree = self.pools if self.kv_layout == "paged" else self.cache_slots
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(tree))

    @property
    def occupancy(self) -> float:
        return self.slots.occupancy
