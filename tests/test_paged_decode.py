"""PagedDecode correctness: the paged decode-attention kernel family,
the int8 KV codec and the fused sampling op.

The contract under test (DESIGN.md §13): routing decode through
`decode_step_paged` (raw pool + block tables, per-slot K/V rows out)
is BIT-IDENTICAL to the legacy `decode_step` on the gathered view, on
both the dense store (identity table) and the paged store — ragged
cursors, GQA and per-layer windows included. int8 KV trades that for a
documented logit-divergence budget and double page capacity.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core.operators import kv_dequantize, kv_quantize
from repro.kernels.paged_attention import (
    paged_decode_attention,
    paged_decode_attention_kernel,
    paged_decode_attention_ref,
)
from repro.kernels.paged_attention.paged_attention import chunk_geometry
from repro.kernels.runtime import ENV_INTERPRET, resolve_interpret
from repro.kernels.sample import sample_last
from repro.models import build
from repro.serve.api import KVSpec
from repro.serve.kvstore import make_kvstore

RNG = np.random.default_rng(0)
INT8_LOGIT_BUDGET = 0.05


def _smoke_model(**overrides):
    cfg = dataclasses.replace(
        get_smoke("tinyllama-1.1b"), dtype=jnp.float32, **overrides
    )
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(1))
    return model, params


def _admit_random(model, stores, lens, max_len):
    """Admit one random batch-1 cache per slot into every store."""
    key = jax.random.PRNGKey(2)
    for slot, n in enumerate(lens):
        key, k1, k2 = jax.random.split(key, 3)
        c1 = model.init_cache(1, int(n))
        c1["k"] = jax.random.normal(k1, c1["k"].shape, jnp.float32).astype(
            c1["k"].dtype
        )
        c1["v"] = jax.random.normal(k2, c1["v"].shape, jnp.float32).astype(
            c1["v"].dtype
        )
        c1["pos"] = jnp.int32(int(n))
        for kv in stores:
            kv.admit(slot, c1, int(n))


# -- op level: Pallas kernel (interpret) vs reference ------------------------


def _table(rng, b, mb, bs, pos, idle=(), holes=()):
    """Block tables over a shuffled pool: each slot maps the blocks that
    hold its first ``pos`` positions, -1 past them; ``idle`` slots are
    all -1, ``holes`` are (slot, entry) set to -1 inside the range."""
    table = rng.permutation(b * mb).astype(np.int32).reshape(b, mb)
    for i, p in enumerate(pos):
        table[i, -(-p // bs):] = -1
    for i in idle:
        table[i] = -1
    for i, j in holes:
        table[i, j] = -1
    return table


# The chunked walk's edges, beside the original ragged batch ("base":
# slot 0 mid-block, slot 1 full cache, slot 2 one token; unused entries
# -1, never dereferenced past pos). With d_kv 32 a chunk is 512 tokens.
KERNEL_GEOMETRIES = {
    "base": dict(bs=8, mb=4, pos=[11, 32, 1],
                 table=lambda rng: np.where(
                     np.arange(4) < [[2], [4], [1]], np.arange(12).reshape(3, 4), -1)),
    # 8-page chunks over a 12-entry table: a masked 4-page tail chunk;
    # windows 7 and 300 start mid-chunk (300 also spans two chunks);
    # slot 0 has a -1 entry inside its range (the zero-block clamp)
    "tail": dict(bs=64, mb=12, pos=[600, 768, 100],
                 table=lambda rng: _table(rng, 3, 12, 64, [600, 768, 100], holes=[(0, 3)])),
    # pos 0, pos on a chunk boundary, an idle slot (pos = mb*bs, all -1)
    "edges": dict(bs=64, mb=12, pos=[0, 512, 768],
                  table=lambda rng: _table(rng, 3, 12, 64, [0, 512, 768], idle=[2])),
    # the dense store's identity table: one block per slot, longer than
    # a chunk, so chunks are row ranges of it
    "dense": dict(bs=1024, mb=1, pos=[0, 512, 700, 1024],
                  table=lambda rng: np.arange(4, dtype=np.int32)[:, None]),
}
KERNEL_CASES = [
    pytest.param(geo, quantized, window,
                 id=("" if geo == "base" else f"{geo}-") + f"{quantized}-{window}")
    for geo in KERNEL_GEOMETRIES
    for window in ([0, 1, 7] if geo == "base" else [0, 1, 7, 300])
    for quantized in (False, True)
]


@pytest.mark.parametrize("geometry,quantized,window", KERNEL_CASES)
def test_kernel_matches_ref(geometry, quantized, window):
    geo = KERNEL_GEOMETRIES[geometry]
    bs, mb, n_kv, rep, hd = geo["bs"], geo["mb"], 2, 4, 16
    b, d_kv = len(geo["pos"]), n_kv * hd
    rows, copies = chunk_geometry(b * mb, bs, mb, d_kv, 1 if quantized else 4)
    if geometry in ("tail", "edges"):
        assert copies > 1 and mb % copies and rows * copies == 512
    if geometry == "dense":
        assert rows < bs and copies == 1
    q = jnp.asarray(RNG.normal(size=(b, 1, n_kv * rep, hd)), jnp.float32)
    kn = jnp.asarray(RNG.normal(size=(b, d_kv)), jnp.float32)
    vn = jnp.asarray(RNG.normal(size=(b, d_kv)), jnp.float32)
    kb = jnp.asarray(RNG.normal(size=(b * mb, bs, d_kv)), jnp.float32)
    vb = jnp.asarray(RNG.normal(size=(b * mb, bs, d_kv)), jnp.float32)
    table = jnp.asarray(geo["table"](RNG), jnp.int32)
    pos = jnp.asarray(geo["pos"], jnp.int32)
    scales = {}
    if quantized:
        kb, ks = kv_quantize(kb)
        vb, vs = kv_quantize(vb)
        scales = {"k_scale": ks, "v_scale": vs}
    args = (q, kn, vn, kb, vb, table, pos)
    kw = dict(n_kv=n_kv, window=window, scale=hd**-0.5, **scales)
    out = _interpreted_kernel(*args, **{**kw, "window": jnp.int32(window)})
    ref = paged_decode_attention_ref(*args, dequant_dtype=jnp.float32, **kw)
    tol = 2e-2 if quantized else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=tol, rtol=tol,
    )


# the window is traced, so the cases of one geometry share a compile
_interpreted_kernel = jax.jit(
    functools.partial(paged_decode_attention_kernel, interpret=True),
    static_argnames=("n_kv", "scale"))


def test_interpret_env_override(monkeypatch):
    monkeypatch.setenv(ENV_INTERPRET, "1")
    assert resolve_interpret(None) is True
    monkeypatch.setenv(ENV_INTERPRET, "0")
    assert resolve_interpret(None) is False
    assert resolve_interpret(True) is True  # explicit arg wins
    monkeypatch.setenv(ENV_INTERPRET, "maybe")
    with pytest.raises(ValueError):
        resolve_interpret(None)
    monkeypatch.delenv(ENV_INTERPRET)
    from repro.kernels.runtime import on_tpu

    assert resolve_interpret(None) is (not on_tpu())  # platform default


# -- model level: decode_step_paged == decode_step, bit for bit --------------


@pytest.mark.parametrize("overrides", [
    {},                                                    # GQA, full causal
    {"attn_kind": "swa", "window": 8, "global_layers": (1,)},  # windowed
])
def test_paged_decode_bitwise(overrides):
    model, params = _smoke_model(**overrides)
    slots, max_len, lens = 3, 32, [5, 12, 20]
    dense_a = make_kvstore(model, slots, max_len, KVSpec(), ragged=True)
    dense_b = make_kvstore(model, slots, max_len, KVSpec(), ragged=True)
    spec = KVSpec(kind="paged", block_size=8,
                  n_blocks=slots * (max_len // 8) + 1)
    paged_a = make_kvstore(model, slots, max_len, spec, ragged=True)
    paged_b = make_kvstore(model, slots, max_len, spec, ragged=True)
    _admit_random(model, [dense_a, dense_b, paged_a, paged_b], lens, max_len)

    legacy = jax.jit(model.decode_step)
    kernelized = jax.jit(model.decode_step_paged)
    active = list(range(slots))
    key = jax.random.PRNGKey(3)
    for _ in range(3):
        key, kt = jax.random.split(key)
        tok = jax.random.randint(kt, (slots, 1), 0, model.cfg.vocab_size,
                                 jnp.int32)
        for ref_kv, new_kv in ((dense_a, dense_b), (paged_a, paged_b)):
            want, cache = legacy(params, ref_kv.view(active), tok)
            ref_kv.absorb(cache, active)
            got, rows_k, rows_v = kernelized(
                params, new_kv.kernel_view(active), tok
            )
            new_kv.absorb_rows(rows_k, rows_v, active)
            np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    # the row scatter wrote the exact bytes the lane-masked absorb wrote
    for ref_kv, new_kv in ((dense_a, dense_b), (paged_a, paged_b)):
        va, vb = ref_kv.view(active), new_kv.view(active)
        np.testing.assert_array_equal(np.asarray(va["k"]), np.asarray(vb["k"]))
        np.testing.assert_array_equal(np.asarray(va["v"]), np.asarray(vb["v"]))


# -- int8 KV codec -----------------------------------------------------------


def test_int8_roundtrip_bounds():
    rows = jnp.asarray(RNG.normal(size=(2, 16, 64)), jnp.float32)
    q8, scale = kv_quantize(rows)
    assert q8.dtype == jnp.int8 and scale.shape == (2, 16)
    back = kv_dequantize(q8, scale, jnp.float32)
    # symmetric per-row scale: error <= scale/2 per element
    err = np.abs(np.asarray(back) - np.asarray(rows))
    bound = np.asarray(scale)[..., None] * 0.5 + 1e-6
    assert (err <= bound).all(), float((err - bound).max())
    # zeros survive exactly (fresh blocks are zeroed in-pool)
    zq, zs = kv_quantize(jnp.zeros((1, 4, 8), jnp.float32))
    assert not np.asarray(zq).any()
    assert not np.asarray(kv_dequantize(zq, zs, jnp.float32)).any()


def test_int8_decode_divergence_budget():
    model, params = _smoke_model()
    slots, max_len, lens = 3, 32, [5, 12, 20]
    dense = make_kvstore(model, slots, max_len, KVSpec(), ragged=True)
    paged8 = make_kvstore(
        model, slots, max_len,
        KVSpec(kind="paged", block_size=8,
               n_blocks=slots * (max_len // 8) * 2 + 1, kv_dtype="int8"),
        ragged=True,
    )
    _admit_random(model, [dense, paged8], lens, max_len)
    legacy = jax.jit(model.decode_step)
    kernelized = jax.jit(model.decode_step_paged)
    active = list(range(slots))
    tok = jnp.zeros((slots, 1), jnp.int32)
    for _ in range(3):
        want, cache = legacy(params, dense.view(active), tok)
        dense.absorb(cache, active)
        got, rows_k, rows_v = kernelized(params, paged8.kernel_view(active), tok)
        paged8.absorb_rows(rows_k, rows_v, active)
        diff = float(np.max(np.abs(np.asarray(want) - np.asarray(got))))
        assert diff < INT8_LOGIT_BUDGET, diff
        tok = sample_last(want)[:, None]


def test_int8_doubles_page_capacity():
    model, _ = _smoke_model()
    fp = make_kvstore(model, 4, 32, KVSpec(kind="paged", block_size=8),
                      ragged=True)
    q8 = make_kvstore(model, 4, 32,
                      KVSpec(kind="paged", block_size=8, kv_dtype="int8"),
                      ragged=True)
    # same pool byte budget (bf16 cache -> 2 bytes/elem), twice the blocks
    assert q8.stats["n_blocks"] - 1 == 2 * (fp.stats["n_blocks"] - 1)
    assert q8.pool_bytes <= fp.pool_bytes


# -- fused sampling ----------------------------------------------------------


def test_sample_last_matches_argmax():
    logits = jnp.asarray(RNG.normal(size=(4, 3, 1000)), jnp.float32)
    want = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(sample_last(logits)),
                                  np.asarray(want))
    np.testing.assert_array_equal(
        np.asarray(sample_last(logits, impl="kernel", interpret=True)),
        np.asarray(want),
    )


def test_sample_last_tie_break_first():
    logits = np.full((1, 1, 1024), -1.0, np.float32)
    logits[0, 0, [3, 699]] = 7.0  # duplicate max across chunk boundary
    logits = jnp.asarray(logits)
    for kw in ({}, {"impl": "kernel", "interpret": True}, {"impl": "ref"}):
        assert int(sample_last(logits, **kw)[0]) == 3, kw


def test_sample_last_topk():
    logits = jnp.asarray(RNG.normal(size=(2, 1, 128)), jnp.float32)
    want = jax.lax.top_k(logits[:, -1], 3)[1].astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(sample_last(logits, k=3)),
                                  np.asarray(want))


# -- the kernel path across DisaggEngine.resize ------------------------------


def test_paged_kernel_across_resize():
    from repro.serve.disagg import DisaggConfig, DisaggEngine
    from repro.serve.engine import Request

    model, params = _smoke_model()
    cfg = DisaggConfig(
        n_prefill_rows=2, decode_slots=4, max_len=32, mode="continuous",
        kv=KVSpec(kind="paged", block_size=8, n_blocks=6 * 4 + 1),
    )
    eng = DisaggEngine(model, params, cfg)
    assert eng._decode_paged is not None
    for i in range(4):
        eng.submit(Request(
            uid=i,
            prompt=RNG.integers(0, model.cfg.vocab_size, 6 + i).astype(np.int32),
            max_new_tokens=8,
        ))
    legacy = jax.jit(model.decode_step)
    kernelized = jax.jit(model.decode_step_paged)

    def assert_parity():
        active = [i for i, s in enumerate(eng.slots) if s is not None]
        assert active
        want, _ = legacy(params, eng.kv.view(active), eng.tokens)
        got, _, _ = kernelized(params, eng.kv.kernel_view(active), eng.tokens)
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))

    for _ in range(3):
        eng.step()
    assert_parity()
    # grow the decode pool mid-flight: table rows move, bytes stay put
    eng.resize(1, 6)
    assert_parity()
    eng.step()
    assert_parity()
    eng.drain(200)
    assert len(eng.finished) == 4
    assert all(len(r.out_tokens) > 0 for r in eng.finished)
