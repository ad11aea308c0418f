"""StreamChannel: the MPIStream communication channel on a TPU mesh.

The paper's channel (Sec. III-A) connects a producer group to a consumer
group; producers inject stream elements as soon as they are ready
(`MPIStream_Isend`) and consumers fold an attached operator over arriving
elements (`MPIStream_Operate`).

TPU realization
---------------
All functions here are *per-device* code, to be called inside a
``jax.shard_map`` body over the grouped axis. Transfers use
``lax.ppermute`` (XLA collective-permute), which the TPU latency-hiding
scheduler turns into async start/done pairs — element ``k+1`` is on the
wire while the operator consumes element ``k``. That is the paper's
asynchronous fine-grained dataflow, with the lockstep-SPMD caveat
documented in DESIGN.md §2 (round-robin wave schedule instead of
first-come-first-served).

Schedule
--------
With C producer rows and R consumer rows, producers are drained in
``ceil(C/R)`` *waves*; each wave streams its ``n_chunks`` elements
through a static permutation (one scan). Wave loops are unrolled in
Python (static perms), chunk loops are ``lax.scan``.

ChannelWire
-----------
Every channel owns a *wire layer* (DESIGN.md §9): a `WireCodec`
(identity / bf16 / int8, see ``repro.core.wire``) applied to whatever
crosses the wire, and — for whole-pytree folds — a chunked,
double-buffered schedule (``chunk_bytes``) that packs the payload into
fixed-size wire chunks and issues chunk ``k+1``'s ``ppermute`` while
chunk ``k`` is being combined. The old all-payload-per-wave path with
its ``optimization_barrier`` is kept as the ``chunk_bytes=None``
fallback (it preserves GSPMD sharding of payload leaves, which packing
does not).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import wire as wirelib
from repro.core.groups import COMPUTE, GroupedMesh

Operator = Callable[[Any, jax.Array, jax.Array], Any]  # (acc, element, k) -> acc

#: wave-combine strategies of the chunked tree fold (see stream_fold_tree)
WAVE_FOLDS = ("kernel", "add", "scan")


def broadcast_from_row(gmesh: GroupedMesh, src_row: int, value: Any) -> Any:
    """Exact broadcast of one row's pytree to every row of the axis.

    Only ``src_row`` contributes to a masked psum, so every leaf keeps
    its dtype and exact bits (integer ids, f64 accumulators and bf16
    payloads all survive; bool goes through int32 and back).
    """
    is_src = lax.axis_index(gmesh.axis) == src_row

    def one(x):
        as_int = x.dtype == jnp.bool_
        y = x.astype(jnp.int32) if as_int else x
        out = lax.psum(jnp.where(is_src, y, jnp.zeros_like(y)), gmesh.axis)
        return out.astype(x.dtype) if as_int else out

    return jax.tree.map(one, value)


@dataclasses.dataclass(frozen=True)
class StreamChannel:
    """A directed channel ``producer -> consumer`` over ``gmesh.axis``.

    ``codec`` and ``chunk_bytes`` are the channel's wire defaults
    (declared per edge on a `ServiceGraph`); both can be overridden per
    fold call. ``codec=None`` means identity; ``chunk_bytes=None`` keeps
    the unchunked whole-payload-per-wave tree fold.
    """

    gmesh: GroupedMesh
    producer: str
    consumer: str
    codec: wirelib.WireCodec | None = None
    chunk_bytes: int | None = None

    # -- static schedule ----------------------------------------------------
    @property
    def n_producers(self) -> int:
        return self.gmesh.group(self.producer).size

    @property
    def n_consumers(self) -> int:
        return self.gmesh.group(self.consumer).size

    @property
    def n_waves(self) -> int:
        return math.ceil(self.n_producers / max(self.n_consumers, 1))

    def wave_perm(self, wave: int) -> list[tuple[int, int]]:
        """Static (src, dst) pairs for one wave (a partial permutation)."""
        prod = list(self.gmesh.rows_of(self.producer))
        cons = list(self.gmesh.rows_of(self.consumer))
        r = len(cons)
        pairs = []
        for j in range(r):
            p = wave * r + j
            if p < len(prod):
                pairs.append((prod[p], cons[j]))
        return pairs

    # -- per-device helpers (inside shard_map) --------------------------------
    def _row(self) -> jax.Array:
        return lax.axis_index(self.gmesh.axis)

    def is_member(self, name: str) -> jax.Array:
        g = self.gmesh.group(name)
        row = self._row()
        return (row >= g.start) & (row < g.stop)

    def member_rank(self, name: str) -> jax.Array:
        """Rank of this row within group `name` (garbage off-group)."""
        return self._row() - self.gmesh.group(name).start

    def _codec(self, codec) -> wirelib.WireCodec:
        return wirelib.get_codec(codec if codec is not None else self.codec)

    # -- the core fold ---------------------------------------------------------
    def stream_fold(
        self,
        elements: jax.Array,
        operator: Operator,
        init: Any,
        *,
        count: jax.Array | None = None,
        waves: Sequence[int] | None = None,
        codec: "wirelib.WireCodec | str | None" = None,
    ) -> Any:
        """Stream producer-local ``elements`` to consumers and fold.

        Parameters
        ----------
        elements : (n_chunks, S) local buffer. Meaningful on producer
            rows only (other rows may pass zeros of the same shape).
        operator : fold fn applied on consumer rows per arriving element.
        init : operator state pytree (same on every row; only consumer
            rows' result is meaningful).
        count : optional per-producer valid-chunk count (dynamic, for
            variable-size streams — the paper's imbalanced producers).
            Elements at index >= count are skipped by masking.
        waves : optional subset of waves to drain (default: all). Lets a
            caller interleave per-wave post-processing — e.g. the
            disaggregated serving step migrates each wave's arriving KV
            cache into a different decode slot before draining the next
            wave of producers.
        codec : wire codec for the element transfer (default: the
            channel's). Elements are encoded once producer-side; each
            arriving wire chunk is decoded before the operator sees it.

        Returns the folded state (valid on consumer rows).

        When ``count`` is None the arrival mask is *static per wave*
        (every chunk of the wave shares ``valid == receives``), so the
        fold runs unconditionally and the result is selected ONCE per
        wave — instead of a per-chunk ``jax.tree.map(where, ...)`` over
        the full accumulator. Operators must therefore tolerate folding
        the all-zeros elements a non-receiving row gets from
        ``ppermute`` (the selected result discards them).
        """
        n_chunks = elements.shape[0]
        axis = self.gmesh.axis
        codec = self._codec(codec)
        if codec.applies(elements.dtype):
            encoded, decode = codec.encode_chunks(elements), codec.decode_chunk
        else:
            encoded, decode = elements, lambda w: w
        is_cons = self.is_member(self.consumer)
        cons_rank = self.member_rank(self.consumer)

        acc = init
        for wave in range(self.n_waves) if waves is None else waves:
            perm = self.wave_perm(wave)
            if not perm:
                continue
            # does this consumer row receive during this wave?
            # (producers need no masking: ppermute ignores non-sources)
            receives = is_cons & (cons_rank < len(perm))

            def chunk_in(k, perm=perm):
                arrived = jax.tree.map(
                    lambda x: lax.ppermute(x[k], axis, perm), encoded
                )
                return decode(arrived)

            if count is None:
                # static mask short-circuit: one select per wave
                def body(carry, k):
                    return operator(carry, chunk_in(k), k), None

                new_acc, _ = lax.scan(body, acc, jnp.arange(n_chunks))
                acc = jax.tree.map(
                    lambda n, o: jnp.where(receives, n, o), new_acc, acc
                )
            else:
                # stream the producer's valid-count alongside (prefix exchange)
                sent_count = lax.ppermute(count, axis, perm)

                def body(carry, k):
                    acc = carry
                    valid = receives & (k < sent_count)
                    new = operator(acc, chunk_in(k), k)
                    acc = jax.tree.map(
                        lambda n, o: jnp.where(valid, n, o), new, acc
                    )
                    return acc, None

                acc, _ = lax.scan(body, acc, jnp.arange(n_chunks))
        return acc

    # -- whole-pytree fold ------------------------------------------------------
    def stream_fold_tree(
        self,
        payload: Any,
        *,
        acc_init: Any | None = None,
        combine: Callable[[Any, Any, jax.Array], Any] | None = None,
        codec: "wirelib.WireCodec | str | None" = None,
        chunk_bytes: int | None = None,
        waves: Sequence[int] | None = None,
        wave_fold: str | None = None,
    ) -> Any:
        """Stream a whole pytree (one element per leaf) and fold on the
        consumer group.

        ``combine(acc, arrived_payload, ok)`` folds one wave; the default
        is a masked elementwise sum (payload structure == acc structure).
        The channel codec encodes the payload on the wire and decodes it
        before ``combine`` sees it, so lossy wires (bf16 / int8) need no
        caller-side plumbing.

        Two schedules:

        * ``chunk_bytes=None`` (default) — the original whole-payload-
          per-wave path. Keeps GSPMD sharding along auto axes (e.g.
          model-sharded gradient leaves), at the cost of a per-wave
          ``optimization_barrier`` that serializes waves to bound memory.
        * ``chunk_bytes=B`` — the ChannelWire chunked schedule: the
          payload is packed (dtype-preserving) into B-byte wire chunks
          and streamed through a double-buffered ``lax.scan`` — chunk
          ``k+1``'s ``ppermute`` is issued while chunk ``k`` is decoded —
          so in-flight transfer memory is bounded to two chunks and the
          barrier (with its lost overlap) is gone. Packing concatenates
          leaves: use it when payload leaves are replicated along auto
          axes or the region is fully manual.

        ``wave_fold`` picks the chunked consumer combine for the default
        sum: ``"kernel"`` stages the wave's decoded chunks and folds them
        with the Pallas ``chunk_accumulate`` kernel (float32 groups),
        ``"add"`` the same staging with a plain vector add, ``"scan"``
        combines each chunk inside the scan (strict two-chunk memory, no
        staging buffer). All three are value-identical.
        """
        codec = self._codec(codec)
        chunk_bytes = chunk_bytes if chunk_bytes is not None else self.chunk_bytes
        if wave_fold is None:
            # the Pallas fast path pays off compiled (TPU) in a fully
            # manual region (a Mosaic call cannot be auto-partitioned);
            # under the CPU interpreter the in-scan combine is both
            # cheapest and memory-strict (the Pallas pass is expensive
            # interpreted)
            from repro.kernels.runtime import on_tpu

            manual = not jax.sharding.get_abstract_mesh().auto_axes
            wave_fold = "kernel" if on_tpu() and manual else "scan"
        if wave_fold not in WAVE_FOLDS:
            raise ValueError(f"wave_fold={wave_fold!r} not in {WAVE_FOLDS}")
        wave_ids = range(self.n_waves) if waves is None else waves
        if chunk_bytes is None:
            return self._fold_tree_barrier(payload, acc_init, combine, codec, wave_ids)
        return self._fold_tree_chunked(
            payload, acc_init, combine, codec, int(chunk_bytes), wave_ids, wave_fold
        )

    def _fold_tree_barrier(self, payload, acc_init, combine, codec, wave_ids):
        """Seed path: full payload per wave, waves serialized."""
        is_cons = self.is_member(self.consumer)
        default_combine = combine is None
        combine = combine or (lambda acc, new, ok: jax.tree.map(
            lambda a, b: jnp.where(ok, a + b, a), acc, new
        ))
        identity = codec.name == "identity"
        sendable = payload if identity else codec.encode_tree(payload)
        acc = (
            jax.tree.map(jnp.zeros_like, payload) if acc_init is None else acc_init
        )
        for wave in wave_ids:
            perm = self.wave_perm(wave)
            if not perm:
                continue
            cons_rank = self.member_rank(self.consumer)
            receives = is_cons & (cons_rank < len(perm))
            arrived = jax.tree.map(
                lambda x: lax.ppermute(x, self.gmesh.axis, perm), sendable
            )
            if not identity:
                arrived = codec.decode_tree(arrived)
            acc = combine(acc, arrived, receives)
            # serialize waves: without this barrier the latency-hiding
            # scheduler hoists every wave's permute-start, keeping
            # n_waves full payload copies in flight (§Perf pair 1 it.6:
            # 214GB -> bounded). Costs overlap; the chunked schedule
            # (chunk_bytes=...) bounds memory without the barrier.
            acc = lax.optimization_barrier(acc)
        if default_combine and not identity:
            # lossy codecs decode to f32 and jnp promotion carries the
            # accumulation in f32; round once at the end so the output
            # dtype matches the accumulator contract (acc_init/payload)
            ref = payload if acc_init is None else acc_init
            acc = jax.tree.map(lambda a, r: a.astype(r.dtype), acc, ref)
        return acc

    def _fold_tree_chunked(
        self, payload, acc_init, combine, codec, chunk_bytes, wave_ids, wave_fold
    ):
        """ChannelWire path: packed chunks, double-buffered transfers."""
        packer = wirelib.WirePacker.plan(payload, chunk_bytes)
        bufs = packer.pack(payload)
        encoded = []  # per group: (wire pytree, per-chunk decode)
        for g, buf in zip(packer.groups, bufs):
            if codec.applies(g.dtype):
                encoded.append((codec.encode_chunks(buf), codec.decode_chunk))
            else:
                encoded.append((buf, lambda w: w))
        is_cons = self.is_member(self.consumer)
        cons_rank = self.member_rank(self.consumer)

        generic = combine is not None
        if generic:
            acc = (
                jax.tree.map(jnp.zeros_like, payload)
                if acc_init is None
                else acc_init
            )
        else:
            start = packer.zeros() if acc_init is None else packer.pack(acc_init)
            # codec-applied groups decode to f32: accumulate in f32 and
            # let unpack round once at the end (per-wave rounding to a
            # narrower group dtype would add untracked error that the
            # f32 error-feedback residual cannot cancel)
            acc_bufs = [
                b.astype(jnp.float32) if codec.applies(g.dtype) else b
                for g, b in zip(packer.groups, start)
            ]
        first = True
        for wave in wave_ids:
            perm = self.wave_perm(wave)
            if not perm:
                continue
            receives = is_cons & (cons_rank < len(perm))
            staged_mode = generic or wave_fold != "scan"
            if staged_mode and not first:
                # gate this wave's sends on the previous wave's combine:
                # without the dependency the scheduler may run every
                # wave's transfer scan up front and keep n_waves decoded
                # staging buffers live — the memory blowup chunking is
                # meant to prevent. At most one wave's staging (plus two
                # wire chunks) is in flight. ("scan" mode serializes
                # naturally through its accumulator carry.)
                anchor = acc if generic else acc_bufs
                anchor, wires = lax.optimization_barrier(
                    (anchor, [enc for enc, _ in encoded])
                )
                encoded = [(w, dec) for w, (_, dec) in zip(wires, encoded)]
                if generic:
                    acc = anchor
                else:
                    acc_bufs = anchor
            first = False
            if staged_mode:
                staged = [
                    self._stream_chunks(enc, dec, g.n_chunks, perm)
                    for (enc, dec), g in zip(encoded, packer.groups)
                ]
            if generic:
                acc = combine(acc, packer.unpack(staged), receives)
                continue
            if wave_fold == "scan":
                for i, ((enc, dec), g) in enumerate(zip(encoded, packer.groups)):
                    acc_bufs[i] = self._stream_chunks_fold(
                        enc, dec, g.n_chunks, perm, acc_bufs[i], receives
                    )
                continue
            for i, (st, g) in enumerate(zip(staged, packer.groups)):
                masked = jnp.where(receives, st, jnp.zeros_like(st))
                if wave_fold == "kernel" and g.dtype == jnp.dtype(jnp.float32):
                    # consumer-side fold fast path: fold acc and the
                    # wave's chunks in one tiled Pallas pass
                    from repro.kernels.stream_reduce.stream_reduce import (
                        chunk_accumulate,
                    )

                    flat = chunk_accumulate(
                        jnp.stack([acc_bufs[i].reshape(-1), masked.reshape(-1)])
                    )
                    acc_bufs[i] = flat.reshape(g.n_chunks, g.chunk_elems)
                else:
                    acc_bufs[i] = acc_bufs[i] + masked.astype(acc_bufs[i].dtype)
        return acc if generic else packer.unpack(acc_bufs)

    def _send_chunk(self, enc, perm, k):
        """ppermute wire chunk ``k`` of one group (all wire leaves)."""
        return jax.tree.map(
            lambda x: lax.ppermute(
                lax.dynamic_index_in_dim(x, k, keepdims=False),
                self.gmesh.axis,
                perm,
            ),
            enc,
        )

    def _stream_chunks(self, enc, dec, n_chunks, perm):
        """Double-buffered transfer of one group's chunks; returns the
        decoded (n_chunks, S) staging buffer. The scan carries only the
        in-flight chunk: iteration ``k`` issues chunk ``k+1``'s
        ``ppermute`` and decodes chunk ``k`` (no data dependence between
        the two, so they overlap), and the last chunk is decoded in an
        epilogue — at most two wire chunks are ever in flight."""
        inflight = self._send_chunk(enc, perm, jnp.zeros((), jnp.int32))
        if n_chunks == 1:
            return dec(inflight)[None]

        def body(infl, k):
            nxt = self._send_chunk(enc, perm, k + 1)
            return nxt, dec(infl)

        last, decoded = lax.scan(body, inflight, jnp.arange(n_chunks - 1))
        return jnp.concatenate([decoded, dec(last)[None]], axis=0)

    def _stream_chunks_fold(self, enc, dec, n_chunks, perm, acc_buf, receives):
        """As `_stream_chunks`, but combines chunk ``k`` into the
        accumulator inside the scan — no staging buffer, strict
        two-chunk in-flight memory."""
        inflight = self._send_chunk(enc, perm, jnp.zeros((), jnp.int32))

        def fold_into(acc_buf, infl, k):
            decd = dec(infl)
            row = jnp.where(receives, decd, jnp.zeros_like(decd))
            cur = lax.dynamic_slice_in_dim(acc_buf, k, 1, 0)
            return lax.dynamic_update_slice_in_dim(
                acc_buf, cur + row[None].astype(acc_buf.dtype), k, 0
            )

        if n_chunks == 1:
            return fold_into(acc_buf, inflight, jnp.zeros((), jnp.int32))

        def body(carry, k):
            acc_buf, infl = carry
            nxt = self._send_chunk(enc, perm, k + 1)
            return (fold_into(acc_buf, infl, k), nxt), None

        (acc_buf, last), _ = lax.scan(
            body, (acc_buf, inflight), jnp.arange(n_chunks - 1)
        )
        return fold_into(acc_buf, last, jnp.full((), n_chunks - 1, jnp.int32))

    # -- result return path -----------------------------------------------------
    def broadcast_from_consumer(self, value: Any) -> Any:
        """Broadcast consumer-row result to every row of the axis.

        Consumer rows hold *identical* values by contract, so only the
        group's first row contributes to a masked psum over the axis —
        every leaf keeps its dtype and exact bits (the old float32
        round-trip with a 1/R rescale did not).
        """
        return broadcast_from_row(
            self.gmesh, self.gmesh.group(self.consumer).start, value
        )

    def scatter_back(self, value: Any, *, wave_of_target: int = 0) -> Any:
        """Reverse-direction transfer: consumer rows send to the
        producer rows of one wave (static inverse permutation)."""
        perm = [(d, s) for (s, d) in self.wave_perm(wave_of_target)]
        return jax.tree.map(
            lambda x: lax.ppermute(x, self.gmesh.axis, perm), value
        )


def make_channel(
    gmesh: GroupedMesh,
    consumer: str,
    producer: str = COMPUTE,
    *,
    codec: "wirelib.WireCodec | str | None" = None,
    chunk_bytes: int | None = None,
) -> StreamChannel:
    """One ad-hoc channel on a bare `GroupedMesh`.

    Migration note: new code should declare its topology once with
    `repro.core.dataflow.ServiceGraph` (stages + edges on one mesh,
    wire options per edge) and obtain channels via
    ``graph.channel(src, dst)``; this one-liner is kept as a thin
    wrapper for single-channel constructions and older call sites.
    """
    return StreamChannel(
        gmesh=gmesh,
        producer=producer,
        consumer=consumer,
        codec=wirelib.get_codec(codec) if codec is not None else None,
        chunk_bytes=chunk_bytes,
    )
