"""Paged single-token decode-attention kernel for TPU.

One decode step of attention for a batch of serving slots, reading the
`PagedKVStore` block pool *directly* through each slot's int32 block
table — no `paged_gather` materialization of a dense (B, S, d) view.

Tiling: grid = (slot, kv_chunk) with the chunk index minor-most, so TPU
walks a slot's blocks sequentially while the running softmax state
(m, l, acc) lives in VMEM scratch. The block table, per-slot cursors
(`pos`) and the layer's attention window ride in as scalar-prefetch
operands (`pltpu.PrefetchScalarGridSpec`) so the K/V BlockSpec index
maps can chase the table: chunk ``j`` of slot ``b`` DMAs pool block
``max(table[b, j], 0)`` straight from HBM (``-1`` = unmapped clamps to
the permanent zero block, matching `operators.paged_gather`).

Masking matches `layers.attention_decode` exactly: pool position ``t``
is live iff ``t < pos[b]`` and, for windowed layers (window > 0),
``t >= pos[b] + 1 - window``; the step's own K/V row (k_new/v_new) is
folded in at the final chunk iff the cursor is still inside the view
(``pos[b] < mb*bs``) — the same "a full cache drops the new row"
semantics as the ragged lane write in `decode_step_lm`. GQA groups the
query heads as (n_kv, group); each KV head is one 2-D score tile.

The dense cache routes through the same kernel with a trivial identity
table (pool = the (B, S, d) cache itself, one block of size S per slot),
so both stores share one code path. int8 pools carry per-row symmetric
scales (nb, bs), applied to the scores (K) and the probabilities (V)
of each chunk in VMEM — dequantization never touches HBM.

CPU CI runs this kernel through the Pallas interpreter
(`resolve_interpret`); numerics are tolerance-matched against
ref.paged_decode_attention_ref, which is itself bitwise against the
legacy gather path. Head/feature dims are not padded to MXU tiles here —
decode tiles are tiny and latency-bound; the Mosaic compiler pads
internally on real hardware.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _kernel(
    # scalar-prefetch operands
    tbl_ref,  # (B, mb) int32 block table
    pos_ref,  # (B,) int32 per-slot cursors
    win_ref,  # (1,) int32 layer attention window (<=0 = full)
    # array operands
    q_ref,    # (1, n_kv, rep, hd) this slot's query, grouped by KV head
    kn_ref,   # (1, n_kv, 1, hd) this step's new K row
    vn_ref,   # (1, n_kv, 1, hd)
    kb_ref,   # (1, bs, d_kv) the table-selected pool block
    vb_ref,   # (1, bs, d_kv)
    *rest,    # [ks_ref, vs_ref,] o_ref, m_scr, l_scr, acc_scr
    bs: int, mb: int, n_kv: int, rep: int, hd: int,
    scale: float, quantized: bool,
):
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest

    b = pl.program_id(0)
    j = pl.program_id(1)
    pos_b = pos_ref[b]
    win = win_ref[0]
    total = mb * bs

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    t = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rep, bs), 1)
    ok = t < pos_b
    ok &= (win <= 0) | (t >= (pos_b + 1) - win)
    k_all = kb_ref[0].astype(jnp.float32)  # (bs, d_kv)
    v_all = vb_ref[0].astype(jnp.float32)
    # one 2-D (rep, hd) x (hd, bs) tile per KV head: the (bs, d_kv) block
    # splits along lanes at head boundaries, never through a transpose
    for g in range(n_kv):
        q = q_ref[0, g].astype(jnp.float32)  # (rep, hd)
        k = k_all[:, g * hd:(g + 1) * hd]    # (bs, hd)
        v = v_all[:, g * hd:(g + 1) * hd]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (rep, bs)
        if quantized:
            # per-row scales (1, bs) commute with the contraction over hd
            s = s * ks_ref[0]
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_scr[g]  # (rep, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[g] = l_scr[g] * alpha + p.sum(axis=-1, keepdims=True)
        pv = p * vs_ref[0] if quantized else p  # V's row scales fold into p
        acc_scr[g] = acc_scr[g] * alpha + jax.lax.dot_general(
            pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[g] = m_new

    @pl.when(j == mb - 1)
    def _fin():
        # fold in the step's own K/V row (position pos_b), then divide.
        # A cursor at/past the view length writes nothing — exactly the
        # lane-masked cache write it replaces. The current token is
        # never length- or window-masked (distance 0 from itself).
        live = pos_b < total
        for g in range(n_kv):
            q = q_ref[0, g].astype(jnp.float32)    # (rep, hd)
            kn = kn_ref[0, g].astype(jnp.float32)  # (1, hd)
            vn = vn_ref[0, g].astype(jnp.float32)
            s_new = (q * kn).sum(axis=-1, keepdims=True) * scale  # (rep, 1)
            s_new = jnp.where(live, s_new, NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, s_new)
            alpha = jnp.exp(m_prev - m_new)
            # gate, don't rely on underflow: when every pool position is
            # masked too, m == NEG_INF and exp(s_new - m) would be 1, not 0
            p_new = jnp.where(live, jnp.exp(s_new - m_new), 0.0)
            l = l_scr[g] * alpha + p_new
            acc = acc_scr[g] * alpha + p_new * vn
            denom = jnp.maximum(l, 1e-30)
            o_ref[0, g] = (acc / denom).astype(o_ref.dtype)


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

    # free XLA reshapes that give every block full (or unit-padded)
    # last two dims, the only small blocks the TPU compiler tiles:
    # head idx = kv_idx * rep + group_idx (layers.attention_decode)
    q4 = q.reshape(b, n_kv, rep, hd)
    kn4 = k_new.reshape(b, n_kv, 1, hd)
    vn4 = v_new.reshape(b, n_kv, 1, hd)
    table = table.astype(jnp.int32)
    pos = pos.astype(jnp.int32)
    win_arr = jnp.asarray(window, jnp.int32).reshape(1)

    # index maps see (grid..., *scalar_refs); chunk j of slot b pulls
    # pool block max(table[b, j], 0) — unmapped chunks read the zero
    # block (paged) or a fully length-masked row (dense identity table).
    def _blk(b_, j, tbl, pos_, win_):
        return (jnp.maximum(tbl[b_, j], 0), 0, 0)

    def _slot(b_, j, tbl, pos_, win_):
        return (b_, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, n_kv, rep, hd), _slot),
        pl.BlockSpec((1, n_kv, 1, hd), _slot),
        pl.BlockSpec((1, n_kv, 1, hd), _slot),
        pl.BlockSpec((1, bs, d_kv), _blk),
        pl.BlockSpec((1, bs, d_kv), _blk),
    ]
    operands = [q4, kn4, vn4, k_blocks, v_blocks]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, bs), _blk), pl.BlockSpec((1, 1, bs), _blk)]
        operands += [k_scale[:, None, :], v_scale[:, None, :]]

    kernel = functools.partial(
        _kernel,
        bs=bs, mb=mb, n_kv=n_kv, rep=rep, hd=hd,
        scale=scale, quantized=quantized,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, n_kv, rep, hd), _slot),
            scratch_shapes=[
                pltpu.VMEM((n_kv, rep, 1), jnp.float32),
                pltpu.VMEM((n_kv, rep, 1), jnp.float32),
                pltpu.VMEM((n_kv, rep, hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_kv, rep, hd), q.dtype),
        interpret=interpret,
    )(table, pos, win_arr, *operands)
    return out.reshape(b, 1, h, hd)
