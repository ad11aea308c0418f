"""KV stores for the serving engines: dense (bit-identical fallback)
and paged (block pool + per-slot block tables + prefix cache).

The engines route every KV access through one of two stores selected by
`api.KVSpec`:

  * `DenseKVStore` — the historic layout: one ``(L, slots, max_len, d)``
    reservation per cache leaf. In ``aligned`` mode it reproduces the
    pre-PR-6 jitted call sequence exactly (same
    `migrate_cache_into_slot` / whole-dict absorb), which is what keeps
    the default engines bit-identical to PR 5. In ``ragged``
    (continuous) mode it additionally tracks per-slot lengths on the
    host and exposes them as the ``(B,)`` decode cursor vector.
  * `PagedKVStore` — KV lives in a pool of ``n_blocks`` fixed-size
    blocks of ``block_size`` tokens; each slot holds a block *table*
    (row of block ids, ``-1`` = unmapped). Decode gathers a slot's
    blocks into a contiguous view (`operators.paged_gather_cache`);
    block 0 is reserved as the permanent zero block and ``-1`` entries
    clamp to it, so the gathered view is bitwise the zero-extended
    dense cache — the invariant behind tests/test_kvstore.py's
    paged-vs-dense identity suite. KV memory scales with *live* tokens:
    blocks are refcounted, allocated on admission/append and returned
    on retire.

The `PrefixCache` rides on the paged store: every admitted prompt
registers its full blocks under prompt-prefix keys (one entry per
full-block boundary, exact token bytes — collision-free), and a later
request from *any* tenant whose prompt starts with the same tokens
reuses those blocks by reference instead of re-prefilling them. Shared
blocks are never written: the engines only append into a slot's tail
block, and a tail block is always freshly allocated (a shared chain
covers full blocks only), so copy-on-write never actually has to copy —
the refcount just keeps a block alive until its last reader retires.

Capacity math (page-aware admission, serve/sched.py): a slot holding
``n`` tokens occupies ``ceil(n / block_size)`` blocks and will need
``ceil(min(n + remaining_new, max_len) / block_size)`` at completion;
the engines reserve that growth before admitting new work, so a decode
step can always allocate its tail block (`absorb` raising "pool
exhausted" means the caller skipped the reservation).
"""
from __future__ import annotations

import dataclasses
import heapq
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.operators import (
    kv_dequantize,
    kv_quantize,
    migrate_cache_into_blocks,
    migrate_cache_into_blocks_int8,
    migrate_cache_into_slot,
    paged_gather,
    paged_gather_cache,
    paged_gather_cache_int8,
)
from repro.obs import trace as _obs
from repro.serve.api import KVSpec

# host spans of the paged store (obs.trace): one track for all of them
_T_KV = ("kv", "store")


def make_kvstore(model, slots: int, max_len: int, spec: KVSpec, *, ragged: bool):
    """Build the KV store a `KVSpec` describes."""
    if spec.kind == "paged":
        return PagedKVStore(model, slots, max_len, spec)
    return DenseKVStore(model, slots, max_len, ragged=ragged)


# ---------------------------------------------------------------------------
# dense store (the historic layout, kept bit-identical)
# ---------------------------------------------------------------------------


class DenseKVStore:
    """One contiguous ``max_len`` reservation per slot.

    ``ragged=False`` (aligned mode) keeps the shared scalar decode
    cursor and the exact PR-5 call sequence. ``ragged=True``
    (continuous mode) tracks per-slot lengths host-side and hands the
    decode step a ``(B,)`` cursor vector: inactive slots get the view
    length as their cursor, so the lane-masked ragged KV write touches
    nothing for them — the property that keeps a continuous dense run
    bitwise comparable to the paged store.
    """

    kind = "dense"
    block_size: int | None = None  # not page-limited

    def __init__(self, model, slots: int, max_len: int, *, ragged: bool = False):
        self._model = model
        self.slots = slots
        self.max_len = max_len
        self.ragged = ragged
        self.cache = model.init_cache(slots, max_len)
        self.lens = np.zeros(slots, np.int64)
        self._mig = jax.jit(migrate_cache_into_slot)
        self._scatter = jax.jit(_dense_scatter_rows)
        self._trunc = jax.jit(_dense_truncate_rows)

    # -- decode surface ----------------------------------------------------
    def view(self, active: Sequence[int] | None = None) -> dict:
        if not self.ragged:
            return self.cache
        pos = np.full(self.slots, self.max_len, np.int32)
        for i in active or ():
            pos[i] = self.lens[i]
        return {"k": self.cache["k"], "v": self.cache["v"],
                "pos": jnp.asarray(pos)}

    def absorb(self, cache: dict, active: Sequence[int]) -> None:
        """Take back the decode step's updated cache."""
        if not self.ragged:
            self.cache = cache
        else:
            self.cache = {"k": cache["k"], "v": cache["v"],
                          "pos": self.cache["pos"]}
        for i in active:
            # both stores cap the cursor at max_len: past it the ragged
            # write lane is empty, so advancing would only desync the
            # rope position between dense and paged runs
            self.lens[i] = min(self.lens[i] + 1, self.max_len)

    def absorb_span(self, cache: dict, active: Sequence[int],
                    n_new: Sequence[int]) -> None:
        """Take back a *verify* step's cache: slot ``active[i]``
        appended ``n_new[i]`` rows starting at its cursor in one batched
        forward (serve/spec.py). Dense is the easy case — the verify
        step wrote straight into the contiguous layout, so absorbing is
        the same whole-dict replacement `absorb` does, plus a multi-row
        cursor bump."""
        if not self.ragged:
            raise RuntimeError("absorb_span needs ragged mode (per-slot cursors)")
        self.cache = {"k": cache["k"], "v": cache["v"],
                      "pos": self.cache["pos"]}
        for i, n in zip(active, n_new):
            self.lens[i] = min(self.lens[i] + int(n), self.max_len)

    def truncate(self, slot: int, new_len: int) -> None:
        """Roll a slot back to ``new_len`` tokens (speculative-decode
        rejection): zero the rows past the new cursor — the ragged
        write lane and the paged-vs-dense identity suite both assume
        everything past a slot's cursor is bitwise zero — and rewind
        the host length. No-op if the slot is already at or below
        ``new_len``."""
        new_len = int(new_len)
        old = int(self.lens[slot])
        if new_len >= old:
            return
        k, v = self._trunc(self.cache["k"], self.cache["v"],
                           jnp.int32(slot), jnp.int32(new_len), jnp.int32(old))
        self.cache = {"k": k, "v": v, "pos": self.cache["pos"]}
        self.lens[slot] = new_len

    # -- paged-kernel surface ----------------------------------------------
    def kernel_view(self, active: Sequence[int] | None = None) -> dict:
        """The dense cache as a trivially-paged pool: one block of
        ``max_len`` tokens per slot, identity block table — the layout
        `decode_step_paged` consumes, so both stores share one decode
        code path (continuous mode only)."""
        if not self.ragged:
            raise RuntimeError("kernel_view needs ragged mode (per-slot cursors)")
        pos = np.full(self.slots, self.max_len, np.int32)
        for i in active or ():
            pos[i] = self.lens[i]
        return {
            "k_pool": self.cache["k"],
            "v_pool": self.cache["v"],
            "tables": jnp.arange(self.slots, dtype=jnp.int32)[:, None],
            "pos": jnp.asarray(pos),
            # dtype exemplar: new rows come back in the cache dtype, the
            # same bits the ragged lane write stored
            "rows_like": jnp.zeros((0,), self.cache["k"].dtype),
        }

    def absorb_rows(self, rows_k: jax.Array, rows_v: jax.Array,
                    active: Sequence[int]) -> None:
        """Write the paged decode step's per-slot K/V rows (L, B, d) at
        each active slot's cursor. Bitwise the lane-masked cache write
        `absorb` took back: the rows are already cast to the cache dtype
        and land at the same (slot, position)."""
        idx = [i for i in active if self.lens[i] < self.max_len]
        if idx:
            k, v = self._scatter(
                self.cache["k"], self.cache["v"], rows_k, rows_v,
                jnp.asarray(idx, jnp.int32),
                jnp.asarray(self.lens[list(idx)], jnp.int32),
            )
            self.cache = {"k": k, "v": v, "pos": self.cache["pos"]}
        for i in active:
            self.lens[i] = min(self.lens[i] + 1, self.max_len)

    # -- admission / retirement --------------------------------------------
    def admit(self, slot: int, cache1: dict, length: int, *,
              tokens=None, logits=None, first=None, uid=None) -> dict:
        self.cache = self._mig(self.cache, cache1, slot)
        self.lens[slot] = length
        return {"prefix_tokens": 0}

    def full_hit(self, tokens):
        return None

    def free(self, slot: int) -> None:
        self.lens[slot] = 0  # KV stays; the next admit zero-extends over it

    # -- capacity ----------------------------------------------------------
    def free_tokens(self) -> int:
        """Honest token capacity: every free slot can hold ``max_len``
        tokens (the dense layout reserves whole slots, so partially
        filled slots contribute nothing). Lets `FleetScheduler.take`'s
        ``free_tokens=`` gate work identically in both KV modes;
        `page_admission_budget` still reports dense stores as
        not-page-limited (the reservation is per slot, not per page)."""
        return int(np.sum(self.lens == 0)) * self.max_len

    def covered_tokens(self, tokens, length: int) -> int:
        return 0

    @property
    def stats(self) -> dict:
        return {"kind": "dense", "live_tokens": int(self.lens.sum()),
                "reserved_tokens": self.slots * self.max_len}

    # -- migration ---------------------------------------------------------
    def slot_cache(self, slot: int) -> dict:
        pos = self.cache["pos"] if not self.ragged else jnp.int32(self.lens[slot])
        return {k: (pos if k == "pos" else v[:, slot : slot + 1])
                for k, v in self.cache.items()}

    def resize(self, new_slots: int, moves: Sequence[tuple[int, int]]):
        """Fresh pool of ``new_slots``; ``moves`` is (dst, src) pairs.
        Same per-slot slice + `migrate_cache_into_slot` sequence the
        PR-5 `DisaggEngine.resize` ran inline (bit-identical)."""
        new = DenseKVStore(self._model, new_slots, self.max_len, ragged=self.ragged)
        for dst, src in moves:
            new.cache = new._mig(new.cache, self.slot_cache(src), dst)
            new.lens[dst] = self.lens[src]
        return new


# ---------------------------------------------------------------------------
# prefix cache (rides on the paged store)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _FullEntry:
    """A whole previously-served prompt: its full blocks by reference
    plus host copies of the tail-block KV rows and the last-position
    logits, so a repeat submission skips prefill entirely."""

    length: int
    blocks: tuple[int, ...]
    k_tail: np.ndarray  # (L, length % bs, d)
    v_tail: np.ndarray
    logits: np.ndarray  # (V,) last-position logits of the cold prefill
    first: int  # greedy first token


class PrefixCache:
    """Prefix-keyed registry of shared KV blocks, LRU-bounded.

    Keys are exact token bytes (``("chain", tokens[:j*bs])`` for every
    full-block boundary j, ``("full", tokens)`` for whole prompts) —
    the hash table's own hashing makes the scheme collision-free.
    Entries hold refcounts on their blocks via the owning store, so a
    block a live slot still reads is never freed by eviction (the store
    only recycles blocks whose count reaches zero).
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self.entries: OrderedDict[tuple, object] = OrderedDict()
        self.hits = 0
        self.misses = 0
        self.hit_tokens = 0

    @staticmethod
    def _key(kind: str, tokens, n: int) -> tuple:
        return (kind, np.asarray(tokens[:n], np.int64).tobytes())

    # -- lookup ------------------------------------------------------------
    def match_chain(self, tokens, length: int, bs: int, *,
                    touch: bool = True) -> tuple[int, ...]:
        """Longest registered chain covering a prefix of ``tokens``
        (full blocks only, at most ``length`` tokens)."""
        for j in range(int(length) // bs, 0, -1):
            key = self._key("chain", tokens, j * bs)
            entry = self.entries.get(key)
            if entry is not None:
                if touch:
                    self.entries.move_to_end(key)
                return entry  # tuple of j block ids
        return ()

    def match_full(self, tokens) -> _FullEntry | None:
        key = self._key("full", tokens, len(tokens))
        entry = self.entries.get(key)
        if entry is not None:
            self.entries.move_to_end(key)
        return entry

    # -- registration ------------------------------------------------------
    def register(self, store: "PagedKVStore", tokens, length: int,
                 row: np.ndarray, cache1=None, logits=None, first=None,
                 uid: int | None = None) -> None:
        bs = store.block_size
        for j in range(1, int(length) // bs + 1):
            key = self._key("chain", tokens, j * bs)
            if key in self.entries:
                self.entries.move_to_end(key)
                continue
            blocks = tuple(int(b) for b in row[:j])
            store._prefix_ref(blocks)
            self.entries[key] = blocks
        if cache1 is not None and logits is not None and first is not None:
            key = self._key("full", tokens, length)
            if key not in self.entries:
                nfull = int(length) // bs
                c = nfull * bs
                blocks = tuple(int(b) for b in row[:nfull])
                store._prefix_ref(blocks)
                # host copies: the host waits for the chip here
                with _obs.span("kv.prefix_copy_wait", _T_KV, uid=uid):
                    k_tail = np.asarray(cache1["k"][:, 0, c:length])
                    v_tail = np.asarray(cache1["v"][:, 0, c:length])
                    logits = np.asarray(logits)
                self.entries[key] = _FullEntry(
                    length=int(length), blocks=blocks, k_tail=k_tail,
                    v_tail=v_tail, logits=logits, first=int(first),
                )
            else:
                self.entries.move_to_end(key)
        while len(self.entries) > self.capacity:
            self.evict_one(store)

    def evict_one(self, store: "PagedKVStore") -> bool:
        if not self.entries:
            return False
        _, entry = self.entries.popitem(last=False)
        blocks = entry.blocks if isinstance(entry, _FullEntry) else entry
        store._prefix_unref(blocks)
        return True


# ---------------------------------------------------------------------------
# paged store
# ---------------------------------------------------------------------------


class PagedKVStore:
    """Block-pooled KV with per-slot block tables.

    Pools are ``(L, n_blocks, block_size, d)``; a slot's table row maps
    view position ``p`` to ``(table[p // bs], p % bs)``. Block 0 is the
    permanent zero block and ``-1`` table entries gather from it, so
    `view` returns exactly the zero-extended dense layout the decode
    step already understands — continuous mode over this store is
    bitwise identical to continuous mode over `DenseKVStore` (asserted
    by tests/test_kvstore.py). Requires ``max_len % block_size == 0``
    so both stores hand decode the same view length.
    """

    kind = "paged"

    def __init__(self, model, slots: int, max_len: int, spec: KVSpec):
        if max_len % spec.block_size:
            raise ValueError(
                f"max_len={max_len} must be a multiple of "
                f"block_size={spec.block_size}"
            )
        probe = jax.eval_shape(lambda: model.init_cache(1, 1))
        if set(probe) != {"k", "v", "pos"}:
            raise ValueError("paged KV needs an attention-only cache (k/v/pos)")
        self._model = model
        self.slots = slots
        self.max_len = max_len
        self.ragged = True
        self.spec = spec
        bs = self.block_size = spec.block_size
        self.max_blocks = mb = max_len // bs
        self.quantized = spec.kv_dtype == "int8"
        self._cache_dtype = probe["k"].dtype  # dequant target / fp pool dtype
        # int8 halves the per-token bytes vs the cache dtype, so the
        # *same pool byte budget* holds itemsize-times the pages — the
        # default capacity scales by that ratio (2x for bf16 caches)
        ratio = np.dtype(self._cache_dtype).itemsize if self.quantized else 1
        n_blocks = (
            spec.n_blocks if spec.n_blocks is not None
            else slots * mb * ratio + 1
        )
        if n_blocks < mb + 1:
            raise ValueError(
                f"n_blocks={n_blocks} cannot hold one full request "
                f"({mb} blocks + the zero block)"
            )
        self.n_blocks = n_blocks
        ln, _, _, dk = probe["k"].shape
        dv = probe["v"].shape[-1]
        pool_dtype = jnp.int8 if self.quantized else self._cache_dtype
        self.k_pool = jnp.zeros((ln, n_blocks, bs, dk), pool_dtype)
        self.v_pool = jnp.zeros((ln, n_blocks, bs, dv), pool_dtype)
        if self.quantized:
            # per-(layer, token-row) symmetric scales (operators.kv_quantize)
            self.k_scale = jnp.zeros((ln, n_blocks, bs), jnp.float32)
            self.v_scale = jnp.zeros((ln, n_blocks, bs), jnp.float32)
        else:
            self.k_scale = self.v_scale = None
        self.tables = np.full((slots, mb), -1, np.int32)
        self.lens = np.zeros(slots, np.int64)
        self.ref = np.zeros(n_blocks, np.int64)
        self.ref[0] = 1  # the zero block is permanently live
        self._pref = np.zeros(n_blocks, np.int64)  # refs held by the prefix cache
        self._free = list(range(1, n_blocks))
        heapq.heapify(self._free)
        self.peak_blocks = 0
        self.prefix = PrefixCache(spec.prefix_capacity) if spec.prefix_cache else None
        if self.quantized:
            self._gather = jax.jit(paged_gather_cache_int8,
                                   static_argnames=("dtype",))
            self._fill = jax.jit(migrate_cache_into_blocks_int8,
                                 static_argnames=("block_size",))
            self._absorb = jax.jit(_absorb_rows_int8)
            self._scatter = jax.jit(_paged_scatter_rows_int8)
            self._trunc_tail = jax.jit(_zero_block_tail_int8)
        else:
            self._gather = jax.jit(paged_gather_cache)
            self._fill = jax.jit(migrate_cache_into_blocks,
                                 static_argnames=("block_size",))
            self._absorb = jax.jit(_absorb_rows)
            self._scatter = jax.jit(_paged_scatter_rows)
            self._trunc_tail = jax.jit(_zero_block_tail)

    # -- block accounting --------------------------------------------------
    def _alloc(self, n: int) -> list[int]:
        while len(self._free) < n and self.prefix is not None:
            if not self.prefix.evict_one(self):
                break
        if len(self._free) < n:
            raise RuntimeError(
                f"KV block pool exhausted: need {n}, "
                f"{len(self._free)}/{self.n_blocks} free "
                "(page-aware admission should have reserved growth)"
            )
        ids = [heapq.heappop(self._free) for _ in range(n)]
        used = self.n_blocks - 1 - len(self._free)
        self.peak_blocks = max(self.peak_blocks, used)
        return ids

    def _decref(self, b: int) -> None:
        self.ref[b] -= 1
        if self.ref[b] == 0:
            heapq.heappush(self._free, b)

    def _prefix_ref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self.ref[b] += 1
            self._pref[b] += 1

    def _prefix_unref(self, blocks: Sequence[int]) -> None:
        for b in blocks:
            self._pref[b] -= 1
            self._decref(b)

    def _evictable_blocks(self) -> int:
        """Blocks held only by prefix entries — reclaimable by LRU
        eviction, so admission counts them as available."""
        return int(np.sum((self._pref > 0) & (self.ref == self._pref)))

    # -- jit dispatch (fp vs int8 pools) ------------------------------------
    def _gather_call(self, tables, pos) -> dict:
        if self.quantized:
            return self._gather(self.k_pool, self.v_pool, self.k_scale,
                                self.v_scale, tables, pos,
                                dtype=self._cache_dtype)
        return self._gather(self.k_pool, self.v_pool, tables, pos)

    def _fill_call(self, cache1: dict, new_ids, *, start: int) -> None:
        ids = jnp.asarray(new_ids, jnp.int32)
        if self.quantized:
            self.k_pool, self.v_pool, self.k_scale, self.v_scale = self._fill(
                self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                cache1, ids, start=start, block_size=self.block_size,
            )
        else:
            self.k_pool, self.v_pool = self._fill(
                self.k_pool, self.v_pool, cache1, ids,
                start=start, block_size=self.block_size,
            )

    # -- decode surface ----------------------------------------------------
    def view(self, active: Sequence[int] | None = None) -> dict:
        pos = np.full(self.slots, self.max_len, np.int32)
        for i in active or ():
            pos[i] = self.lens[i]
        return self._gather_call(jnp.asarray(self.tables), jnp.asarray(pos))

    def absorb(self, cache: dict, active: Sequence[int]) -> None:
        """Write the decode step's appended rows back into the pool.

        The decode step wrote slot ``i``'s new K/V at view position
        ``lens[i]`` — extract that row and store it at the mapped
        (block, offset). A slot whose cursor crosses a block boundary
        gets a fresh tail block, zeroed in the same jitted call before
        the row lands (a recycled block holds a retired request's data,
        and the dense comparison expects zeros past the cursor).
        """
        idx, pos, blocks, offs, fresh = self._tail_slots(active)
        if idx:
            args = (
                jnp.asarray(idx, jnp.int32), jnp.asarray(pos, jnp.int32),
                jnp.asarray(blocks, jnp.int32), jnp.asarray(offs, jnp.int32),
                jnp.asarray(fresh, jnp.int32),
            )
            if self.quantized:
                (self.k_pool, self.v_pool, self.k_scale,
                 self.v_scale) = self._absorb(
                    self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                    cache["k"], cache["v"], *args,
                )
            else:
                self.k_pool, self.v_pool = self._absorb(
                    self.k_pool, self.v_pool, cache["k"], cache["v"], *args,
                )
        for i in active:
            self.lens[i] = min(self.lens[i] + 1, self.max_len)

    def absorb_span(self, cache: dict, active: Sequence[int],
                    n_new: Sequence[int]) -> None:
        """Take back a *verify* step's cache: slot ``active[i]``
        appended ``n_new[i]`` rows starting at its cursor
        (serve/spec.py). The verify step wrote the chunk rows at view
        positions ``lens[i] + j`` — exactly where `absorb` extracts
        from once the cursor has advanced ``j`` times — so a span
        absorb is ``max(n_new)`` plain absorbs over the still-live
        subset, reusing the tail-block alloc/zeroing path unchanged
        (which is what keeps the refcount accounting identical to
        one-token decode)."""
        counts = [int(n) for n in n_new]
        for j in range(max(counts, default=0)):
            self.absorb(cache, [i for i, n in zip(active, counts) if n > j])

    def truncate(self, slot: int, new_len: int) -> None:
        """Roll a slot back to ``new_len`` tokens (speculative-decode
        rejection): blocks wholly past the keep point are dereferenced
        (table entry back to ``-1`` — "rollback truncates block
        tables"), and the kept partial boundary block, if any, has its
        rows past the cursor zeroed. That boundary block is always a
        decode-appended *private* block (shared prefix chains cover
        full prompt blocks only, and the cursor at tick start is past
        the prompt), so the zeroing can't be seen by another reader —
        asserted, not assumed. No-op at or below ``new_len``."""
        new_len = int(new_len)
        old = int(self.lens[slot])
        if new_len >= old:
            return
        bs = self.block_size
        first_dead = -(-new_len // bs)  # ceil: first block index fully rejected
        for b_idx in range(first_dead, self.max_blocks):
            b = int(self.tables[slot, b_idx])
            if b > 0:
                self._decref(b)
                self.tables[slot, b_idx] = -1
        rem = new_len % bs
        if rem:
            b = int(self.tables[slot, new_len // bs])
            assert b > 0 and self.ref[b] == 1, (
                f"truncate boundary block {b} must be private (ref="
                f"{self.ref[b] if b > 0 else 'zero-block'})"
            )
            args = (jnp.int32(b), jnp.int32(rem))
            if self.quantized:
                (self.k_pool, self.v_pool, self.k_scale,
                 self.v_scale) = self._trunc_tail(
                    self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                    *args,
                )
            else:
                self.k_pool, self.v_pool = self._trunc_tail(
                    self.k_pool, self.v_pool, *args,
                )
        self.lens[slot] = new_len

    def _tail_slots(self, active: Sequence[int]):
        """Host half of a decode append: the slots whose cursor is still
        inside the view, their (block, offset) targets, and any freshly
        allocated tail blocks (block-boundary crossings)."""
        idx = [i for i in active if self.lens[i] < self.max_len]
        if not idx:
            return idx, None, None, None, None
        fresh = []
        for i in idx:
            b = int(self.lens[i]) // self.block_size
            if self.tables[i, b] < 0:
                (nb,) = self._alloc(1)
                self.ref[nb] = 1
                self.tables[i, b] = nb
                fresh.append(nb)
        pos = self.lens[list(idx)]
        blocks = self.tables[list(idx), pos // self.block_size]
        offs = pos % self.block_size
        return idx, pos, blocks, offs, fresh

    # -- paged-kernel surface ----------------------------------------------
    def kernel_view(self, active: Sequence[int] | None = None) -> dict:
        """The raw pool + block tables for `decode_step_paged`: no
        gather, no dense materialization — the kernel chases the table
        per block. int8 pools ride with their scale sidecars."""
        with _obs.span("kv.kernel_view", _T_KV) as sp:
            pos = np.full(self.slots, self.max_len, np.int32)
            for i in active or ():
                pos[i] = self.lens[i]
            # the blocks the decode kernel walks for the active slots,
            # beside the size of the whole tables
            live = -(-pos[list(active or ())] // self.block_size)
            sp.set(live_blocks=int(live.sum()), table_blocks=self.tables.size)
            out = {
                "k_pool": self.k_pool,
                "v_pool": self.v_pool,
                "tables": jnp.asarray(self.tables),
                "pos": jnp.asarray(pos),
                # new rows (and the int8 dequant target) use the cache
                # dtype, matching what the view/lane-write path stored
                "rows_like": jnp.zeros((0,), self._cache_dtype),
            }
        if self.quantized:
            out["k_scale"] = self.k_scale
            out["v_scale"] = self.v_scale
        return out

    def absorb_rows(self, rows_k: jax.Array, rows_v: jax.Array,
                    active: Sequence[int]) -> None:
        """Scatter the paged decode step's per-slot K/V rows (L, B, d)
        into each active slot's tail block — the kernel-path `absorb`,
        minus the view round-trip. int8 pools quantize the rows here
        (per-row symmetric scale) before the scatter; fresh tail blocks
        are zeroed in the same jitted call."""
        with _obs.span("kv.absorb_rows", _T_KV) as sp:
            idx, pos, blocks, offs, fresh = self._tail_slots(active)
            if idx:
                sp.set(fresh=len(fresh))
                args = (
                    jnp.asarray(idx, jnp.int32),
                    jnp.asarray(blocks, jnp.int32), jnp.asarray(offs, jnp.int32),
                    jnp.asarray(fresh, jnp.int32),
                )
                if self.quantized:
                    (self.k_pool, self.v_pool, self.k_scale,
                     self.v_scale) = self._scatter(
                        self.k_pool, self.v_pool, self.k_scale, self.v_scale,
                        rows_k, rows_v, *args,
                    )
                else:
                    self.k_pool, self.v_pool = self._scatter(
                        self.k_pool, self.v_pool, rows_k, rows_v, *args,
                    )
            for i in active:
                self.lens[i] = min(self.lens[i] + 1, self.max_len)

    # -- admission / retirement --------------------------------------------
    def admit(self, slot: int, cache1: dict, length: int, *,
              tokens=None, logits=None, first=None,
              uid: int | None = None) -> dict:
        """Install a prefilled request: shared prefix blocks by
        reference, the rest filled from ``cache1``
        (`migrate_cache_into_blocks`). ``tokens`` enables prefix
        lookup/registration; ``logits``/``first`` additionally register
        the whole prompt for the skip-prefill fast path. ``uid`` names
        the request in the trace."""
        with _obs.span("kv.admit", _T_KV, uid=uid) as sp:
            length = int(length)
            shared: tuple[int, ...] = ()
            if self.prefix is not None and tokens is not None:
                shared = self.prefix.match_chain(tokens, length, self.block_size)
            start = len(shared) * self.block_size
            # take the slot's references on shared blocks BEFORE allocating:
            # _alloc may evict prefix entries, and an unreferenced shared
            # block would land on the free list mid-admission
            for b in shared:
                self.ref[b] += 1
            n_new = -((start - length) // self.block_size) if length > start else 0
            new_ids = self._alloc(n_new)
            if n_new:
                self._fill_call(cache1, new_ids, start=start)
            row = np.full(self.max_blocks, -1, np.int32)
            row[: len(shared)] = shared
            row[len(shared) : len(shared) + n_new] = new_ids
            for b in new_ids:
                self.ref[b] = 1
            self.tables[slot] = row
            self.lens[slot] = length
            if self.prefix is not None and tokens is not None:
                self.prefix.hit_tokens += start
                if start:
                    self.prefix.hits += 1
                else:
                    self.prefix.misses += 1
                self.prefix.register(self, tokens, length, row,
                                     cache1=cache1, logits=logits, first=first,
                                     uid=uid)
            sp.set(prefix_tokens=start)
            return {"prefix_tokens": start}

    def full_hit(self, tokens) -> _FullEntry | None:
        if self.prefix is None:
            return None
        return self.prefix.match_full(tokens)

    def admit_from_full(self, slot: int, entry: _FullEntry, *,
                        uid: int | None = None) -> dict:
        """Install a whole cached prompt without running prefill: full
        blocks by reference, the tail rows from the entry's host copy
        into a fresh private block. ``uid`` names the request in the
        trace."""
        with _obs.span("kv.admit_from_full", _T_KV, uid=uid,
                       prefix_tokens=entry.length):
            row = np.full(self.max_blocks, -1, np.int32)
            row[: len(entry.blocks)] = entry.blocks
            for b in entry.blocks:
                self.ref[b] += 1
            rem = entry.length - len(entry.blocks) * self.block_size
            if rem:
                (nb,) = self._alloc(1)
                tail = {"k": jnp.asarray(entry.k_tail)[:, None],
                        "v": jnp.asarray(entry.v_tail)[:, None],
                        "pos": jnp.int32(rem)}
                self._fill_call(tail, [nb], start=0)
                self.ref[nb] = 1
                row[len(entry.blocks)] = nb
            self.tables[slot] = row
            self.lens[slot] = entry.length
            self.prefix.hits += 1
            self.prefix.hit_tokens += entry.length
            return {"prefix_tokens": entry.length}

    def free(self, slot: int) -> None:
        for b in self.tables[slot]:
            if b > 0:
                self._decref(int(b))
        self.tables[slot] = -1
        self.lens[slot] = 0

    # -- capacity ----------------------------------------------------------
    def free_tokens(self) -> int:
        return (len(self._free) + self._evictable_blocks()) * self.block_size

    def covered_tokens(self, tokens, length: int) -> int:
        """Prefix tokens a future admit would get for free (no LRU
        touch) — the page-aware admission discount."""
        if self.prefix is None:
            return 0
        return len(
            self.prefix.match_chain(tokens, int(length), self.block_size,
                                    touch=False)
        ) * self.block_size

    @property
    def blocks_in_use(self) -> int:
        return self.n_blocks - 1 - len(self._free)

    @property
    def pool_bytes(self) -> int:
        """K/V *data* bytes (the budget int8 halves per token; the f32
        scale sidecar — 4B per token row per layer — is reported
        separately in stats)."""
        return self.k_pool.size * self.k_pool.dtype.itemsize + \
            self.v_pool.size * self.v_pool.dtype.itemsize

    @property
    def stats(self) -> dict:
        out = {
            "kind": "paged",
            "kv_dtype": self.spec.kv_dtype,
            "pool_bytes": self.pool_bytes,
            "scale_bytes": 0 if not self.quantized else (
                self.k_scale.size + self.v_scale.size
            ) * 4,
            "block_size": self.block_size,
            "n_blocks": self.n_blocks,
            "blocks_in_use": self.blocks_in_use,
            "peak_blocks": self.peak_blocks,
            "evictable_blocks": self._evictable_blocks(),
            "live_tokens": int(self.lens.sum()),
            "live_block_demand": int(sum(
                -(-int(n) // self.block_size) for n in self.lens if n
            )),
            # total refcount over the pool (slots + prefix entries,
            # excluding the permanently-live zero block) and the prefix
            # cache's share — the obs registry mirrors both, so refcount
            # leaks show up as a drifting gauge, not just a failed test
            "ref_total": int(self.ref.sum()) - 1,
            "prefix_ref_total": int(self._pref.sum()),
        }
        if self.prefix is not None:
            out.update(prefix_hits=self.prefix.hits,
                       prefix_misses=self.prefix.misses,
                       prefix_hit_tokens=self.prefix.hit_tokens,
                       prefix_entries=len(self.prefix.entries))
        return out

    # -- migration ---------------------------------------------------------
    def slot_cache(self, slot: int) -> dict:
        """A slot as a batch-1 dense cache (cross-store migration);
        int8 pools dequantize on the way out."""
        table1 = jnp.asarray(self.tables[slot : slot + 1])
        if self.quantized:
            view = self._gather_call(table1, jnp.asarray([self.lens[slot]],
                                                         jnp.int32))
            return {"k": view["k"], "v": view["v"],
                    "pos": jnp.int32(self.lens[slot])}
        return {"k": paged_gather(self.k_pool, table1),
                "v": paged_gather(self.v_pool, table1),
                "pos": jnp.int32(self.lens[slot])}

    def resize(self, new_slots: int, moves: Sequence[tuple[int, int]]):
        """Re-size the slot pool by *table moves* — no KV bytes copied;
        the block pool is shared state and in-flight requests keep
        their blocks. Slots not named as a source are freed."""
        new_tables = np.full((new_slots, self.max_blocks), -1, np.int32)
        new_lens = np.zeros(new_slots, np.int64)
        moved = set()
        for dst, src in moves:
            new_tables[dst] = self.tables[src]
            new_lens[dst] = self.lens[src]
            moved.add(src)
        for i in range(self.slots):
            if i not in moved:
                for b in self.tables[i]:
                    if b > 0:
                        self._decref(int(b))
        self.tables, self.lens, self.slots = new_tables, new_lens, new_slots
        return self


def _absorb_rows(k_pool, v_pool, view_k, view_v, slot_idx, positions,
                 blocks, offs, fresh):
    """Extract each active slot's newly-decoded row from the gathered
    view and scatter it into the pool; ``fresh`` blocks (just allocated
    tail blocks, possibly recycled) are zeroed first so everything past
    a slot's cursor stays bitwise zero like the dense layout."""
    k_pool = k_pool.at[:, fresh].set(0)
    v_pool = v_pool.at[:, fresh].set(0)
    sel = positions.reshape(1, -1, 1, 1)
    rows_k = jnp.take_along_axis(jnp.take(view_k, slot_idx, axis=1), sel,
                                 axis=2)[:, :, 0]
    rows_v = jnp.take_along_axis(jnp.take(view_v, slot_idx, axis=1), sel,
                                 axis=2)[:, :, 0]
    return (k_pool.at[:, blocks, offs].set(rows_k),
            v_pool.at[:, blocks, offs].set(rows_v))


def _absorb_rows_int8(k_pool, v_pool, k_scale, v_scale, view_k, view_v,
                      slot_idx, positions, blocks, offs, fresh):
    """int8 `_absorb_rows`: extract the fp rows from the dequantized
    view, re-quantize per row, scatter data + scales."""
    k_pool = k_pool.at[:, fresh].set(0)
    v_pool = v_pool.at[:, fresh].set(0)
    k_scale = k_scale.at[:, fresh].set(0)
    v_scale = v_scale.at[:, fresh].set(0)
    sel = positions.reshape(1, -1, 1, 1)
    rows_k = jnp.take_along_axis(jnp.take(view_k, slot_idx, axis=1), sel,
                                 axis=2)[:, :, 0]
    rows_v = jnp.take_along_axis(jnp.take(view_v, slot_idx, axis=1), sel,
                                 axis=2)[:, :, 0]
    kq, ks = kv_quantize(rows_k)
    vq, vs = kv_quantize(rows_v)
    return (k_pool.at[:, blocks, offs].set(kq),
            v_pool.at[:, blocks, offs].set(vq),
            k_scale.at[:, blocks, offs].set(ks),
            v_scale.at[:, blocks, offs].set(vs))


def _paged_scatter_rows(k_pool, v_pool, rows_k, rows_v, slot_idx, blocks,
                        offs, fresh):
    """Kernel-path append: the decode step hands back its per-slot K/V
    rows (L, B, d) directly — select the active ones and scatter, no
    gathered view to extract from. Fresh tail blocks are zeroed first
    (recycled blocks hold a retired request's data and the dense
    comparison expects zeros past the cursor)."""
    k_pool = k_pool.at[:, fresh].set(0)
    v_pool = v_pool.at[:, fresh].set(0)
    return (k_pool.at[:, blocks, offs].set(rows_k[:, slot_idx]),
            v_pool.at[:, blocks, offs].set(rows_v[:, slot_idx]))


def _paged_scatter_rows_int8(k_pool, v_pool, k_scale, v_scale, rows_k,
                             rows_v, slot_idx, blocks, offs, fresh):
    k_pool = k_pool.at[:, fresh].set(0)
    v_pool = v_pool.at[:, fresh].set(0)
    k_scale = k_scale.at[:, fresh].set(0)
    v_scale = v_scale.at[:, fresh].set(0)
    kq, ks = kv_quantize(rows_k[:, slot_idx])
    vq, vs = kv_quantize(rows_v[:, slot_idx])
    return (k_pool.at[:, blocks, offs].set(kq),
            v_pool.at[:, blocks, offs].set(vq),
            k_scale.at[:, blocks, offs].set(ks),
            v_scale.at[:, blocks, offs].set(vs))


def _dense_truncate_rows(k_cache, v_cache, slot, lo, hi):
    """Zero one slot's rows in [lo, hi) — speculative rollback keeps
    the zeros-past-cursor invariant the lane write and the paged
    identity suite depend on."""
    pos = jnp.arange(k_cache.shape[2])
    keep = (pos < lo) | (pos >= hi)
    kslot = jnp.where(keep[:, None], k_cache[:, slot], 0)
    vslot = jnp.where(keep[:, None], v_cache[:, slot], 0)
    return k_cache.at[:, slot].set(kslot), v_cache.at[:, slot].set(vslot)


def _zero_block_tail(k_pool, v_pool, block, start):
    """Zero one block's rows in [start, block_size) — the kept partial
    boundary block of a speculative rollback."""
    keep = jnp.arange(k_pool.shape[2]) < start
    kb = jnp.where(keep[:, None], k_pool[:, block], 0)
    vb = jnp.where(keep[:, None], v_pool[:, block], 0)
    return k_pool.at[:, block].set(kb), v_pool.at[:, block].set(vb)


def _zero_block_tail_int8(k_pool, v_pool, k_scale, v_scale, block, start):
    keep = jnp.arange(k_pool.shape[2]) < start
    kb = jnp.where(keep[:, None], k_pool[:, block], 0)
    vb = jnp.where(keep[:, None], v_pool[:, block], 0)
    ks = jnp.where(keep, k_scale[:, block], 0)
    vs = jnp.where(keep, v_scale[:, block], 0)
    return (k_pool.at[:, block].set(kb), v_pool.at[:, block].set(vb),
            k_scale.at[:, block].set(ks), v_scale.at[:, block].set(vs))


def _dense_scatter_rows(k_cache, v_cache, rows_k, rows_v, slot_idx, positions):
    """Dense kernel-path append: slot ``slot_idx[i]``'s row lands at
    sequence position ``positions[i]`` — the same (value, place) the
    ragged lane write produced, so the cache stays bitwise identical."""
    return (k_cache.at[:, slot_idx, positions].set(rows_k[:, slot_idx]),
            v_cache.at[:, slot_idx, positions].set(rows_v[:, slot_idx]))


__all__ = ["DenseKVStore", "PagedKVStore", "PrefixCache", "make_kvstore"]
