"""Fused last-token argmax kernel for TPU.

Greedy sampling used to be a full-vocab `jnp.argmax` over a logits
tensor XLA had already materialized; fused here it streams the last
position's vocab row chunk-by-chunk through VMEM, carrying a running
(max, first-index) pair per row in scratch — one pass over V bytes, no
intermediate. Grid = (vocab_chunk,); every step covers the whole
batch. Tie-break matches `jnp.argmax`: the *first* maximal index wins
(strict ``>`` across chunks, the least index of the max within one).

Only k=1 (the serving hot path) runs in the kernel; `ops.sample_last`
handles k>1 with `jax.lax.top_k` on the sliced last row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _kernel(x_ref, o_ref, m_scr, i_scr, *, block: int, nchunks: int, vocab: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        i_scr[...] = jnp.zeros_like(i_scr)

    x = x_ref[...].astype(jnp.float32)  # (B, block)
    idx = j * block + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    x = jnp.where(idx < vocab, x, -jnp.inf)  # mask the padded tail
    cm = jnp.max(x, axis=-1, keepdims=True)
    # first index of the chunk max (argmax's tie-break), as a min over lanes
    ci = jnp.min(jnp.where(x == cm, idx, jnp.iinfo(jnp.int32).max), axis=-1,
                 keepdims=True)
    better = cm > m_scr[...]
    m_scr[...] = jnp.where(better, cm, m_scr[...])
    i_scr[...] = jnp.where(better, ci, i_scr[...])

    @pl.when(j == nchunks - 1)
    def _fin():
        o_ref[...] = i_scr[...]


def argmax_last_kernel(
    last: jax.Array,  # (B, V) — logits of the last position
    *,
    block: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Streaming argmax over the vocab axis -> (B,) int32.

    Each grid step reads a ``(B, block)`` tile of every row at once and
    the result is a ``(B, 1)`` column: block dims that are full or a
    multiple of 128, as the TPU compiler requires."""
    interpret = resolve_interpret(interpret)
    b, vocab = last.shape
    block = min(block, vocab)
    nchunks = -(-vocab // block)
    pad = nchunks * block - vocab
    if pad:
        last = jnp.pad(last, ((0, 0), (0, pad)))
    kernel = functools.partial(_kernel, block=block, nchunks=nchunks, vocab=vocab)
    out = pl.pallas_call(
        kernel,
        grid=(nchunks,),
        in_specs=[pl.BlockSpec((b, block), lambda j: (0, j))],
        out_specs=pl.BlockSpec((b, 1), lambda j: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((b, 1), jnp.float32),
            pltpu.VMEM((b, 1), jnp.int32),
        ],
        interpret=interpret,
    )(last)
    return out[:, 0]


__all__ = ["argmax_last_kernel"]
