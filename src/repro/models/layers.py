"""Shared neural-net layers (pure functional JAX, no framework deps).

Conventions
-----------
* Params are nested dicts of jnp arrays; init fns take an `rng` and
  return the dict; apply fns take (params, inputs).
* Compute dtype is configurable (bf16 default); params kept in fp32,
  cast at use (mixed precision, master weights for the optimizer).
* Attention uses a *flattened* KV layout (..., n_kv * head_dim) so the
  flattened feature dim shards over the `model` axis regardless of
  whether n_kv divides the axis (see DESIGN.md §4).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
import numpy as np

Params = dict


# -- sharding hints -----------------------------------------------------------------

def _ambient_mesh():
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def maybe_shard(x: jax.Array, *entries) -> jax.Array:
    """with_sharding_constraint against the ambient mesh, if any.

    Entries: axis name, "batch" (resolves to the present data axes, i.e.
    ("pod","data") or ("data",)), or None. Silently skipped when no mesh
    is set (smoke tests) or when a sharded dim doesn't divide — so model
    code can state intent unconditionally.
    """
    mesh = _ambient_mesh()
    if mesh is None:
        return x
    names = set(mesh.axis_names)
    sizes = dict(zip(mesh.axis_names, mesh.axis_sizes))
    resolved = []
    for e in entries:
        if e == "batch":
            t = tuple(a for a in ("pod", "data") if a in names)
            resolved.append(t if t else None)
        elif isinstance(e, str):
            resolved.append(e if e in names else None)
        else:
            resolved.append(None)
    for dim, e in zip(x.shape, resolved):
        if e is None:
            continue
        axes = e if isinstance(e, tuple) else (e,)
        total = 1
        for a in axes:
            total *= sizes[a]
        if dim % total:
            return x  # non-divisible: leave placement to GSPMD
    from jax.sharding import PartitionSpec as _P

    return jax.lax.with_sharding_constraint(x, _P(*resolved))


# -- initializers ----------------------------------------------------------------

def _dense_init(key, shape, scale: float | None = None):
    fan_in = shape[0]
    scale = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(jnp.float32)


def init_linear(key, d_in: int, d_out: int, bias: bool = False) -> Params:
    p = {"w": _dense_init(key, (d_in, d_out))}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def linear(p: Params, x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    y = jnp.einsum("...i,io->...o", x.astype(dtype), p["w"].astype(dtype))
    from repro.utils import flags

    if flags.bf16_wire() and dtype == jnp.bfloat16:
        # pin the partial-sum dtype at the TP boundary: GSPMD then
        # all-reduces 2-byte activations instead of hoisting the f32
        # upcast (for the norm) above the reduce (§Perf iteration 1)
        y = jax.lax.optimization_barrier(y)
    if "b" in p:
        y = y + p["b"].astype(dtype)
    return y


# -- norms ------------------------------------------------------------------------

def init_norm(d: int, kind: str) -> Params:
    p = {"scale": jnp.ones((d,), jnp.float32)}
    if kind == "ln":
        p["bias"] = jnp.zeros((d,), jnp.float32)
    return p


def apply_norm(p: Params, x: jax.Array, kind: str, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    if kind == "rms":
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    elif kind == "ln":
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
    else:
        raise ValueError(kind)
    y = y * p["scale"]
    if "bias" in p:
        y = y + p["bias"]
    return y.astype(x.dtype)


# -- rotary embeddings --------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, n_heads, head_dim); positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -- attention masks -----------------------------------------------------------------

NEG_INF = -1e30


def causal_window_mask(
    q_pos: jax.Array, k_pos: jax.Array, window: jax.Array | int
) -> jax.Array:
    """(q, k) additive mask: causal + optional sliding window.

    window <= 0 means unlimited (full causal). `window` may be a traced
    per-layer scalar so heterogeneous layer stacks scan uniformly.
    """
    dist = q_pos[:, None] - k_pos[None, :]
    ok = dist >= 0
    window = jnp.asarray(window)
    ok = ok & ((window <= 0) | (dist < window))
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def chunked_local_mask(q_pos: jax.Array, k_pos: jax.Array, chunk: int) -> jax.Array:
    """llama4-style chunked local attention: attend within the same chunk
    (causal)."""
    same = (q_pos[:, None] // chunk) == (k_pos[None, :] // chunk)
    ok = same & (q_pos[:, None] >= k_pos[None, :])
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


# -- attention cores -----------------------------------------------------------------

def _expand_kv(k: jax.Array, n_heads: int, n_kv: int) -> jax.Array:
    """(B,S,n_kv,hd) -> (B,S,n_heads,hd) by group repetition (GQA)."""
    if n_kv == n_heads:
        return k
    rep = n_heads // n_kv
    return jnp.repeat(k, rep, axis=2)


def attention_plain(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Sk, Kv, hd)
    v: jax.Array,  # (B, Sk, Kv, hd)
    mask: jax.Array,  # (Sq, Sk) additive
    softmax_scale: float,
) -> jax.Array:
    n_heads, n_kv = q.shape[2], k.shape[2]
    k = _expand_kv(k, n_heads, n_kv)
    v = _expand_kv(v, n_heads, n_kv)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
    logits = logits * softmax_scale + mask[None, None]
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def attention_blockwise(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_positions: jax.Array,  # (Sq,)
    k_positions: jax.Array,  # (Sk,)
    window: jax.Array | int,
    softmax_scale: float,
    kv_block: int | None = None,
) -> jax.Array:
    """Flash-style streaming softmax over KV blocks (pure jnp; the
    Pallas kernel in kernels/flash_attention mirrors this tiling).

    Memory is O(Sq * kv_block) instead of O(Sq * Sk) — required for the
    32k prefill and 4k train shapes at production batch sizes.
    """
    from repro.utils import flags

    if kv_block is None:
        kv_block = flags.kv_block(1024)
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    n_kv = k.shape[2]
    k = _expand_kv(k, h, n_kv)
    v = _expand_kv(v, h, n_kv)
    nblk = -(-sk // kv_block)
    pad = nblk * kv_block - sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-(10**9))
    kb = k.reshape(b, nblk, kv_block, h, hd)
    vb = v.reshape(b, nblk, kv_block, h, hd)
    kpb = k_positions.reshape(nblk, kv_block)

    def body(carry, inp):
        m, l, acc = carry  # (B,H,Sq), (B,H,Sq), (B,H,Sq,hd)
        kblk, vblk, kpos = inp
        logits = jnp.einsum("bqhd,bkhd->bhqk", q, kblk).astype(jnp.float32)
        logits = logits * softmax_scale + causal_window_mask(q_positions, kpos, window)[
            None, None
        ]
        m_new = jnp.maximum(m, logits.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(logits - m_new[..., None])
        l_new = l * alpha + p.sum(axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(q.dtype), vblk
        ).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    init = (
        jnp.full((b, h, sq), -jnp.inf, jnp.float32),
        jnp.zeros((b, h, sq), jnp.float32),
        jnp.zeros((b, h, sq, hd), jnp.float32),
    )
    (m, l, acc), _ = jax.lax.scan(body, init, (kb.swapaxes(0, 1), vb.swapaxes(0, 1), kpb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.swapaxes(1, 2).astype(q.dtype)  # (B,Sq,H,hd)


def attention_decode(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, S, Kv*hd) flattened layout
    v_cache: jax.Array,
    n_kv: int,
    valid_len: jax.Array,  # scalar or (B,)
    window: jax.Array | int,
    softmax_scale: float,
) -> jax.Array:
    """Single-token decode against a flattened KV cache.

    The cache stays in its sharded flattened layout; GQA expansion is an
    einsum-side reshape on the *query* instead of repeating KV
    (q grouped: (B, g, Kv, hd) x (B, S, Kv, hd)), so no materialized
    repeat of the big cache.
    """
    b, _, h, hd = q.shape
    s = k_cache.shape[1]
    g = h // n_kv
    # head idx = kv_idx * g + group_idx (matches _expand_kv's jnp.repeat)
    qg = q[:, 0].reshape(b, n_kv, g, hd)
    kc = k_cache.reshape(b, s, n_kv, hd)
    vc = v_cache.reshape(b, s, n_kv, hd)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, kc).astype(jnp.float32) * softmax_scale
    pos = jnp.arange(s)
    valid = pos[None, :] < jnp.reshape(valid_len, (-1, 1))
    window = jnp.asarray(window)
    in_window = (window <= 0) | (
        pos[None, :] >= jnp.reshape(valid_len, (-1, 1)) - window
    )
    ok = valid & in_window
    logits = jnp.where(ok[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgs,bskd->bkgd", probs, vc)
    return out.reshape(b, 1, h, hd)


def attention_verify(
    q: jax.Array,  # (B, S, H, hd) — one chunk of draft positions per slot
    k_cache: jax.Array,  # (B, Sc, Kv*hd) flattened layout
    v_cache: jax.Array,
    n_kv: int,
    valid_len: jax.Array,  # (B, S) per-chunk-position live lengths
    window: jax.Array | int,
    softmax_scale: float,
) -> jax.Array:
    """`attention_decode` over a whole speculative chunk at once.

    Chunk position ``j`` of slot ``b`` attends the cache prefix
    ``[0, valid_len[b, j])`` — the verify step writes the chunk's K/V
    first, then every position sees exactly the prefix the sequential
    decode step would have seen, with identical masking (`NEG_INF` into
    the same softmax/weighted-sum reductions). That per-element identity
    is what carries the engines' bitwise decode contract over to the
    batched verify (tests/test_spec.py::test_verify_matches_sequential).
    """
    b, sq, h, hd = q.shape
    s = k_cache.shape[1]
    g = h // n_kv
    # head idx = kv_idx * g + group_idx (matches _expand_kv's jnp.repeat)
    qg = q.reshape(b, sq, n_kv, g, hd)
    kc = k_cache.reshape(b, s, n_kv, hd)
    vc = v_cache.reshape(b, s, n_kv, hd)
    logits = (
        jnp.einsum("bjkgd,bskd->bjkgs", qg, kc).astype(jnp.float32)
        * softmax_scale
    )
    pos = jnp.arange(s)
    valid = pos[None, None, :] < valid_len[:, :, None]
    window = jnp.asarray(window)
    in_window = (window <= 0) | (
        pos[None, None, :] >= valid_len[:, :, None] - window
    )
    ok = valid & in_window
    logits = jnp.where(ok[:, :, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bjkgs,bskd->bjkgd", probs, vc)
    return out.reshape(b, sq, h, hd)


# -- MLPs --------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, kind: str, bias: bool = False) -> Params:
    ks = jax.random.split(key, 3)
    if kind == "swiglu":
        return {
            "w_gate": init_linear(ks[0], d_model, d_ff, bias),
            "w_up": init_linear(ks[1], d_model, d_ff, bias),
            "w_down": init_linear(ks[2], d_ff, d_model, bias),
        }
    if kind == "gelu":
        return {
            "w_up": init_linear(ks[0], d_model, d_ff, bias),
            "w_down": init_linear(ks[1], d_ff, d_model, bias),
        }
    raise ValueError(kind)


def apply_mlp(p: Params, x: jax.Array, kind: str, dtype=jnp.bfloat16) -> jax.Array:
    if kind == "swiglu":
        h = jax.nn.silu(linear(p["w_gate"], x, dtype)) * linear(p["w_up"], x, dtype)
    elif kind == "gelu":
        h = jax.nn.gelu(linear(p["w_up"], x, dtype))
    else:
        raise ValueError(kind)
    return linear(p["w_down"], h, dtype)


# -- embeddings -----------------------------------------------------------------------

def init_embedding(key, vocab: int, d_model: int) -> Params:
    return {"table": _dense_init(key, (vocab, d_model), scale=0.02)}


def embed(p: Params, tokens: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return p["table"].astype(dtype)[tokens]


def unembed(p: Params, x: jax.Array, dtype=jnp.bfloat16) -> jax.Array:
    return jnp.einsum("...d,vd->...v", x.astype(dtype), p["table"].astype(dtype))


def sinusoidal_at(pos: jax.Array, d_model: int) -> jax.Array:
    """Sinusoidal embedding row for a traced position (decode path)."""
    dim = jnp.arange(0, d_model, 2, jnp.float32)
    angle = pos.astype(jnp.float32) / jnp.power(10000.0, dim / d_model)
    out = jnp.zeros((d_model,), jnp.float32)
    out = out.at[0::2].set(jnp.sin(angle))
    out = out.at[1::2].set(jnp.cos(angle))
    return out


def sinusoidal_positions(seq: int, d_model: int) -> jax.Array:
    pos = np.arange(seq)[:, None]
    dim = np.arange(0, d_model, 2)[None, :]
    angle = pos / np.power(10000.0, dim / d_model)
    out = np.zeros((seq, d_model), np.float32)
    out[:, 0::2] = np.sin(angle)
    out[:, 1::2] = np.cos(angle)
    return jnp.asarray(out)
