"""Paged single-token decode-attention kernel for TPU.

One decode step of attention for a batch of serving slots, reading the
`PagedKVStore` block pool *directly* through each slot's int32 block
table — no `paged_gather` materialization of a dense (B, S, d) view.

Walk: grid = (slot,). The K/V pools stay in HBM; each grid step walks
only the chunks of its slot that hold live positions, with a
`lax.fori_loop`, and copies the pages of a chunk itself: one
`pltpu.make_async_copy` per table entry, into a double-buffered VMEM
scratch. The next chunk's copies start before the current chunk is
computed; after a slot's last chunk the next slot's first chunk starts,
so the walk streams across grid steps. A chunk that lies wholly inside
the live range starts its copies with no test per entry and waits once
per pool; the chunks at the range's ends test each entry. Live pool
positions of slot ``b`` are ``[lo, hi)``: ``hi = min(pos[b], mb*bs)``,
``lo = max(0, pos[b] + 1 - window)`` for windowed layers (window > 0)
and 0 otherwise — the mask of `layers.attention_decode`. No chunk
outside that range, and no page of a chunk outside it, is copied or
computed. A ``-1`` entry inside the range copies the permanent zero
block (`operators.paged_gather`'s clamp). A slot with nothing to attend
(a full cache under a window of 1) walks its whole view masked: the
reference's softmax over it is uniform.

Chunk size (`chunk_geometry`) comes from the input shapes, not from a
setting: about `CHUNK_TOKENS` tokens, with the double-buffered K and V
chunks held to `CHUNK_VMEM_BYTES` in the pool dtype. Where a block is
shorter than a chunk, a chunk is that many whole blocks (the tail chunk
of a table whose length it does not divide is masked); where one block
holds more than a chunk — the dense store's identity table, one
``max_len`` block per slot — a chunk is a lane-aligned row range of it.

Inside a chunk the math is unchanged: float32 streaming softmax (m, l,
acc carried by the loop), GQA grouping of the query heads as (n_kv,
group) with one 2-D score tile per KV head, int8 pools dequantized in
VMEM by their per-row scales (on the scores for K, on the
probabilities for V). A copy moves whole 128-lane tiles and a block's
scales are fewer, so the wrapper gathers each slot's scales into token
order, one HBM row per chunk, and the kernel copies a chunk's row with
its pages. After the last chunk the step's own K/V row
(k_new/v_new) is folded in iff the cursor is still inside the view
(``pos[b] < mb*bs``) — "a full cache drops the new row", as the ragged
lane write in `decode_step_lm`.

CPU CI runs this kernel through the Pallas interpreter
(`resolve_interpret`); numerics are tolerance-matched against
ref.paged_decode_attention_ref, which is itself bitwise against the
legacy gather path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30
CHUNK_TOKENS = 512  # tokens a chunk aims for
CHUNK_VMEM_BYTES = 1 << 20  # K and V chunk buffers, both slots, pool dtype
LANES = 128


def chunk_geometry(nb: int, bs: int, mb: int, d_kv: int, itemsize: int) -> tuple[int, int]:
    """``(rows, copies)`` of one chunk: each copy moves ``rows``
    consecutive rows of one pool block, and a chunk is ``copies``
    copies, ``rows * copies`` tokens. A chunk spans no more blocks than
    a table holds, nor than the pool (a wait names that many)."""
    tokens = max(1, min(CHUNK_TOKENS, CHUNK_VMEM_BYTES // (4 * d_kv * itemsize)))
    if bs <= tokens:
        return bs, max(1, min(tokens // bs, mb, nb))
    # a block longer than a chunk: the chunk is a row range of it, a
    # multiple of the lane width so the scale rows copy whole tiles
    rows = next((r for r in range(tokens - tokens % LANES, 0, -LANES) if bs % r == 0), bs)
    return rows, 1


def _kernel(
    # scalar-prefetch operands
    tbl_ref,  # (B, mb) int32 block table
    pos_ref,  # (B,) int32 per-slot cursors
    win_ref,  # (1,) int32 layer attention window (<=0 = full)
    # array operands
    q_ref,    # (1, n_kv, rep, hd) this slot's query, grouped by KV head
    kn_ref,   # (1, n_kv, 1, hd) this step's new K row
    vn_ref,   # (1, n_kv, 1, hd)
    k_hbm,    # (nb, bs, d_kv) the pools, left in HBM
    v_hbm,
    *rest,    # [ks_hbm, vs_hbm,] o_ref, k_buf, v_buf, [ks_buf, vs_buf,] sems, nxt_ref
    bs: int, mb: int, rows: int, copies: int, n_slots: int, n_chunks: int,
    n_kv: int, rep: int, hd: int, scale: float, quantized: bool,
):
    if quantized:
        ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems, nxt_ref = rest
    else:
        o_ref, k_buf, v_buf, sems, nxt_ref = rest
        ks_hbm = vs_hbm = ks_buf = vs_buf = None

    b = pl.program_id(0)
    win = win_ref[0]
    total = mb * bs
    chunk = rows * copies

    @pl.when(b == 0)
    def _init():
        # Copies a chunk skips leave older rows in its buffer. They are
        # masked, but a probability of 0 times a NaN is still NaN: give
        # V finite contents once, before any copy.
        v_buf[...] = jnp.zeros_like(v_buf)
        nxt_ref[0] = -1

    def live(slot):
        """The slot's live pool positions [lo, hi), the walked range
        [w_lo, w_hi), its first chunk and its chunk count."""
        p = pos_ref[slot]
        hi = jnp.minimum(p, total)
        lo = jnp.where(win > 0, jnp.clip(p + 1 - win, 0, hi), 0)
        # Nothing to attend (a full cache under a window of 1): the
        # reference's softmax over an all-masked view is uniform, the
        # mean of V over every position, so walk the whole view masked.
        empty = (hi <= lo) & (p >= total)
        w_lo = jnp.where(empty, 0, lo)
        w_hi = jnp.where(empty, total, hi)
        first = w_lo // chunk
        n = jnp.where(w_hi > w_lo, (w_hi + chunk - 1) // chunk - first, 0)
        return lo, hi, w_lo, w_hi, first, n

    def copy_all(slot, c, buf, lo, hi, op):
        """Start (op "start") or wait for (op "wait") chunk c of a slot
        into buffer slot buf: one copy per table entry that holds a
        position of [lo, hi), and the chunk's int8 scales."""

        def start(i):
            t0 = (c * copies + i) * rows
            blk = jnp.maximum(tbl_ref[slot, t0 // bs], 0)
            src = (blk,) if rows == bs else (blk, pl.ds(t0 % bs, rows))
            pltpu.make_async_copy(k_hbm.at[src], k_buf.at[buf, i], sems.at[0, buf]).start()
            pltpu.make_async_copy(v_hbm.at[src], v_buf.at[buf, i], sems.at[1, buf]).start()

        def wait(i, count=1):
            # a wait takes its byte count from the destination: one wait
            # per pool covers `count` consecutive copies
            src = (pl.ds(0, count), pl.ds(0, rows))
            dst = (buf, pl.ds(i, count))
            pltpu.make_async_copy(k_hbm.at[src], k_buf.at[dst], sems.at[0, buf]).wait()
            pltpu.make_async_copy(v_hbm.at[src], v_buf.at[dst], sems.at[1, buf]).wait()

        # A chunk inside the range copies every entry: no test per entry,
        # one wait per pool. Only the chunks at its ends test each one.
        inside = (c * chunk >= lo) & ((c + 1) * chunk <= hi)

        @pl.when(inside)
        def _inside():
            if op == "start":
                for i in range(copies):
                    start(i)
            else:
                wait(0, copies)

        @pl.when(~inside)
        def _edge():
            act = start if op == "start" else wait
            for i in range(copies):
                t0 = (c * copies + i) * rows
                pl.when((t0 < hi) & (t0 + rows > lo))(functools.partial(act, i))

        if quantized:
            # the chunk's scales, already in token order (see the wrapper)
            row = slot * n_chunks + c
            for d in (pltpu.make_async_copy(ks_hbm.at[row], ks_buf.at[buf], sems.at[0, buf]),
                      pltpu.make_async_copy(vs_hbm.at[row], vs_buf.at[buf], sems.at[1, buf])):
                getattr(d, op)()

    lo, hi, w_lo, w_hi, first, n = live(b)
    # the previous grid step may have started this slot's first chunk
    pre = nxt_ref[0]
    buf0 = jnp.maximum(pre, 0)

    @pl.when((n > 0) & (pre < 0))
    def _start_first():
        copy_all(b, first, buf0, w_lo, w_hi, "start")

    nb_ = jnp.minimum(b + 1, n_slots - 1)
    _, _, lo_n, hi_n, first_n, n_n = live(nb_)
    go_next = (b + 1 < n_slots) & (n_n > 0)
    q = [q_ref[0, g].astype(jnp.float32) for g in range(n_kv)]  # (rep, hd)

    def body(c, carry):
        ms, ls, accs = carry
        buf = (buf0 + c - first) % 2
        last = c + 1 == first + n

        @pl.when(~last)
        def _prefetch():
            copy_all(b, c + 1, 1 - buf, w_lo, w_hi, "start")

        @pl.when(last & go_next)
        def _prefetch_next_slot():
            copy_all(nb_, first_n, 1 - buf, lo_n, hi_n, "start")

        copy_all(b, c, buf, w_lo, w_hi, "wait")
        t = c * chunk + jax.lax.broadcasted_iota(jnp.int32, (rep, chunk), 1)
        ok = (t >= lo) & (t < hi)
        # masked view positions score NEG_INF (weight 0 beside a live
        # one, 1 in a uniform softmax); positions past the view, in a
        # tail chunk, -inf (weight 0 always)
        fill = jnp.where(t < total, NEG_INF, -jnp.inf)
        k_all = k_buf[buf].astype(jnp.float32).reshape(chunk, n_kv * hd)
        v_all = v_buf[buf].astype(jnp.float32).reshape(chunk, n_kv * hd)
        if quantized:
            k_sc = ks_buf[buf][:, :chunk]  # (1, chunk)
            v_sc = vs_buf[buf][:, :chunk]
        out = ([], [], [])
        # one 2-D (rep, hd) x (hd, chunk) tile per KV head: the (chunk,
        # d_kv) rows split along lanes at head boundaries, no transpose
        for g in range(n_kv):
            k = k_all[:, g * hd:(g + 1) * hd]  # (chunk, hd)
            v = v_all[:, g * hd:(g + 1) * hd]
            s = jax.lax.dot_general(
                q[g], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # (rep, chunk)
            if quantized:
                # per-row scales (1, chunk) commute with the contraction over hd
                s = s * k_sc
            s = jnp.where(ok, s, fill)
            m_new = jnp.maximum(ms[g], s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(ms[g] - m_new)
            p = jnp.exp(s - m_new)
            pv = p * v_sc if quantized else p  # V's row scales fold into p
            out[0].append(m_new)
            out[1].append(ls[g] * alpha + p.sum(axis=-1, keepdims=True))
            out[2].append(accs[g] * alpha + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
            ))
        return out

    init = ([jnp.full((rep, 1), -jnp.inf, jnp.float32)] * n_kv,
            [jnp.zeros((rep, 1), jnp.float32)] * n_kv,
            [jnp.zeros((rep, hd), jnp.float32)] * n_kv)
    ms, ls, accs = jax.lax.fori_loop(first, first + n, body, init)
    nxt_ref[0] = jnp.where(go_next & (n > 0), (buf0 + n) % 2, -1)

    # fold in the step's own K/V row (position pos_b), then divide. A
    # cursor at/past the view length writes nothing — exactly the
    # lane-masked cache write it replaces. The current token is never
    # length- or window-masked (distance 0 from itself).
    new_live = pos_ref[b] < total
    for g in range(n_kv):
        kn = kn_ref[0, g].astype(jnp.float32)  # (1, hd)
        vn = vn_ref[0, g].astype(jnp.float32)
        s_new = (q[g] * kn).sum(axis=-1, keepdims=True) * scale  # (rep, 1)
        s_new = jnp.where(new_live, s_new, NEG_INF)
        m_new = jnp.maximum(ms[g], s_new)
        alpha = jnp.exp(ms[g] - m_new)
        # gate, don't rely on underflow: when every pool position is
        # masked too, m == NEG_INF and exp(s_new - m) would be 1, not 0
        p_new = jnp.where(new_live, jnp.exp(s_new - m_new), 0.0)
        l = ls[g] * alpha + p_new
        acc = accs[g] * alpha + p_new * vn
        o_ref[0, g] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_kernel(
    q: jax.Array,        # (B, 1, H, hd)
    k_new: jax.Array,    # (B, d_kv)
    v_new: jax.Array,    # (B, d_kv)
    k_blocks: jax.Array, # (nb, bs, d_kv) fp or int8 pool, one layer
    v_blocks: jax.Array,
    table: jax.Array,    # (B, mb) int32
    pos: jax.Array,      # (B,) int32
    *,
    n_kv: int,
    window: jax.Array | int,
    scale: float,
    k_scale: jax.Array | None = None,  # (nb, bs) f32 — int8 pools only
    v_scale: jax.Array | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Streaming-softmax decode attention over a block pool.

    Returns (B, 1, H, hd) in q.dtype. ``window`` may be a traced scalar
    (per-layer windows ride through `lax.scan`).
    """
    interpret = resolve_interpret(interpret)
    b, one, h, hd = q.shape
    assert one == 1, "decode kernel takes a single query token per slot"
    assert h % n_kv == 0, "GQA requires n_heads % n_kv == 0"
    rep = h // n_kv
    nb, bs, d_kv = k_blocks.shape
    assert d_kv == n_kv * hd
    mb = table.shape[1]
    quantized = k_blocks.dtype == jnp.int8
    if quantized and (k_scale is None or v_scale is None):
        raise ValueError("int8 KV blocks need k_scale/v_scale")
    rows, copies = chunk_geometry(nb, bs, mb, d_kv, k_blocks.dtype.itemsize)
    chunk = rows * copies
    n_chunks = -(-mb * bs // chunk)

    # free XLA reshapes that give every block full (or unit-padded)
    # last two dims, the only small blocks the TPU compiler tiles:
    # head idx = kv_idx * rep + group_idx (layers.attention_decode)
    q4 = q.reshape(b, n_kv, rep, hd)
    kn4 = k_new.reshape(b, n_kv, 1, hd)
    vn4 = v_new.reshape(b, n_kv, 1, hd)
    table = table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)

    def _slot(b_, tbl, pos_, win_):
        return (b_, 0, 0, 0)

    hbm = pl.BlockSpec(memory_space=pl.ANY)
    in_specs = [
        pl.BlockSpec((1, n_kv, rep, hd), _slot),
        pl.BlockSpec((1, n_kv, 1, hd), _slot),
        pl.BlockSpec((1, n_kv, 1, hd), _slot),
        hbm, hbm,
    ]
    operands = [q4, kn4, vn4, k_blocks, v_blocks]
    buf = (2, copies, rows, d_kv)
    scratch = [pltpu.VMEM(buf, k_blocks.dtype), pltpu.VMEM(buf, v_blocks.dtype)]
    if quantized:
        # A copy's lanes must be whole 128-lane tiles, and a block's bs
        # scales are fewer: gather the slot's scales in token order, one
        # row per chunk, and copy a chunk's row with its pages.
        lanes = -(-chunk // LANES) * LANES

        def by_chunk(sc):
            flat = jnp.take(sc, jnp.maximum(table, 0), axis=0).reshape(b, mb * bs)
            flat = jnp.pad(flat, ((0, 0), (0, n_chunks * chunk - mb * bs)))
            rows_ = flat.reshape(b * n_chunks, 1, chunk)
            return jnp.pad(rows_, ((0, 0), (0, 0), (0, lanes - chunk)))

        in_specs += [hbm, hbm]
        operands += [by_chunk(k_scale), by_chunk(v_scale)]
        scratch += [pltpu.VMEM((2, 1, lanes), jnp.float32)] * 2
    # (K, V) x buffer slot; the buffer slot that holds the next grid
    # step's first chunk (-1: not started)
    scratch += [pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)]

    kernel = functools.partial(
        _kernel,
        bs=bs, mb=mb, rows=rows, copies=copies, n_slots=b, n_chunks=n_chunks,
        n_kv=n_kv, rep=rep, hd=hd, scale=scale, quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_kv, rep, hd), _slot),
            scratch_shapes=scratch,
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep, hd), q.dtype),
        # grid steps hand DMAs and the buffer slot on to the next one
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(table, pos, win_arr, *operands)
    return out.reshape(b, 1, h, hd)
