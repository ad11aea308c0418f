"""Keyed stream-reduce (histogram) kernel for TPU — the consumer-side
operator of the paper's decoupled reduce (MapReduce case study).

GPU histograms scatter with atomics; TPUs have no scatter-atomics, so
the TPU-native adaptation (DESIGN.md §6) turns the keyed reduction into
an MXU matmul: each tile of (keys, counts) builds a one-hot comparison
against a bin-id tile and contracts counts^T @ onehot into a VMEM
accumulator. Grid = (num_bin_tiles, num_element_tiles) — element index
minor-most so the accumulator persists in scratch per bin tile.

Also provides `chunk_accumulate`, the grad-chunk sum operator used by
the decoupled reducer group, tiled the trivial way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.runtime import resolve_interpret


def _hist_kernel(keys_ref, counts_ref, o_ref, acc_scr, *, tile_elems, tile_bins, n_tiles_e):
    bi = pl.program_id(0)
    ei = pl.program_id(1)

    @pl.when(ei == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    keys = keys_ref[...].astype(jnp.int32)      # (tile_elems,)
    counts = counts_ref[...].astype(jnp.float32)
    bin_ids = bi * tile_bins + jax.lax.broadcasted_iota(
        jnp.int32, (tile_elems, tile_bins), 1
    )
    onehot = (keys[:, None] == bin_ids).astype(jnp.float32)  # (E, Bins)
    # counts^T @ onehot on the MXU: (1,E) x (E,Bins) -> (1,Bins)
    acc_scr[...] = acc_scr[...] + jax.lax.dot_general(
        counts[None, :], onehot, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )[0]

    @pl.when(ei == n_tiles_e - 1)
    def _fin():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def histogram(
    keys: jax.Array,    # (N,) int32, negative = padding
    counts: jax.Array,  # (N,) float
    n_bins: int,
    *,
    tile_elems: int = 512,
    tile_bins: int = 512,
    interpret: bool | None = None,
) -> jax.Array:
    interpret = resolve_interpret(interpret)
    n = keys.shape[0]
    tile_elems = min(tile_elems, max(n, 1))
    n_e = -(-n // tile_elems)
    pad_e = n_e * tile_elems - n
    if pad_e:
        keys = jnp.pad(keys, (0, pad_e), constant_values=-1)
        counts = jnp.pad(counts, (0, pad_e))
    tile_bins = min(tile_bins, n_bins)
    n_b = -(-n_bins // tile_bins)
    padded_bins = n_b * tile_bins

    kernel = functools.partial(
        _hist_kernel, tile_elems=tile_elems, tile_bins=tile_bins, n_tiles_e=n_e
    )
    out = pl.pallas_call(
        kernel,
        grid=(n_b, n_e),
        in_specs=[
            pl.BlockSpec((tile_elems,), lambda b_, e_: (e_,)),
            pl.BlockSpec((tile_elems,), lambda b_, e_: (e_,)),
        ],
        out_specs=pl.BlockSpec((tile_bins,), lambda b_, e_: (b_,)),
        out_shape=jax.ShapeDtypeStruct((padded_bins,), jnp.float32),
        scratch_shapes=[pltpu.VMEM((tile_bins,), jnp.float32)],
        interpret=interpret,
    )(keys, counts)
    return out[:n_bins]


def _acc_kernel(elems_ref, o_ref):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # the f32 output block stays resident across the chunk axis
    o_ref[...] += elems_ref[0].astype(jnp.float32)


def chunk_accumulate(
    elements: jax.Array,  # (n_chunks, S)
    *,
    tile: int = 1024,
    interpret: bool | None = None,
) -> jax.Array:
    """Sum stream elements: out[j] = sum_k elements[k, j] (the reducer
    group's gradient-chunk fold), tiled over S.

    Blocks are ``(1, 1, tile)`` over a ``(n_chunks, 1, S)`` view and the
    output is ``(1, S)``, so the last two block dims are a full dim and a
    multiple of 128, as the TPU compiler requires."""
    interpret = resolve_interpret(interpret)
    n_chunks, s = elements.shape
    tile = min(tile, s)
    n_t = -(-s // tile)
    pad = n_t * tile - s
    if pad:
        elements = jnp.pad(elements, ((0, 0), (0, pad)))
    out = pl.pallas_call(
        _acc_kernel,
        grid=(n_t, n_chunks),
        in_specs=[pl.BlockSpec((1, 1, tile), lambda t_, c_: (c_, 0, t_))],
        out_specs=pl.BlockSpec((1, tile), lambda t_, c_: (0, t_)),
        out_shape=jax.ShapeDtypeStruct((1, n_t * tile), jnp.float32),
        interpret=interpret,
    )(elements[:, None, :])
    return out[0, :s]
