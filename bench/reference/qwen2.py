"""Plain reference of the Qwen2 architecture (Qwen1.5 and Qwen2.5 share
it): decoder-only, RMSNorm, rotary positions (rotate-half), grouped-query
attention with biases on q/k/v, SwiGLU MLP, tied unembedding.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``; no kernels, cache or batching tricks. The weights
are drawn again from the seed by ``bench.weights`` under the parameter
names the served tree uses; nothing is read from the program.

``precision="fp8"`` is the control: every linear layer's input and
weight (the unembedding's too) rounded to float8 e4m3 with one scale per
tensor, then multiplied in float32. For training the rounding passes the
gradient straight through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0  # largest finite float8_e4m3fn


def dims(cfg: dict) -> dict:
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    hd = cfg.get("head_dim") or d // h
    return {"d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": hd,
            "ff": cfg["intermediate_size"], "V": cfg["vocab_size"],
            "L": cfg["num_hidden_layers"], "eps": cfg["rms_norm_eps"],
            "theta": cfg["rope_theta"]}


def leaf_shapes(cfg: dict) -> dict:
    """Parameter names and shapes, in the served tree's naming."""
    m = dims(cfg)
    d, L, dq, dkv, ff = m["d"], m["L"], m["h"] * m["hd"], m["kv"] * m["hd"], m["ff"]
    return {
        "embed/table": (m["V"], d),
        "final_norm/scale": (d,),
        "layers/norm1/scale": (L, d),
        "layers/norm2/scale": (L, d),
        "layers/attn/wq/w": (L, d, dq), "layers/attn/wq/b": (L, dq),
        "layers/attn/wk/w": (L, d, dkv), "layers/attn/wk/b": (L, dkv),
        "layers/attn/wv/w": (L, d, dkv), "layers/attn/wv/b": (L, dkv),
        "layers/attn/wo/w": (L, dq, d),
        "layers/mlp/w_gate/w": (L, d, ff),
        "layers/mlp/w_up/w": (L, d, ff),
        "layers/mlp/w_down/w": (L, ff, d),
    }


def make_weights(cfg: dict, seed: int, stored_dtype) -> dict:
    """Every leaf as the seed draws it, in the dtype it is stored in
    (``stored_dtype(name)``), in one jitted call."""
    shapes = leaf_shapes(cfg)

    @jax.jit
    def build(key):
        return {n: weights.leaf(key, n, s, stored_dtype(n)) for n, s in shapes.items()}

    return build(weights.base_key(seed))


def _round_fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _qdq(x):
    return _round_fp8(x)


_qdq.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


def _mm(x, w, precision: str):
    x = x.astype(jnp.float32)
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _qdq(x), _qdq(w)
    return jnp.matmul(x, w, precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (..., S, heads, hd); positions (S,)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _layer(m, precision, x, w, positions):
    """One decoder layer on x (B, S, d) float32; w holds this layer's
    leaves (any stored dtype)."""
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    b, s, _ = x.shape
    h = _rms(x, f32(w["layers/norm1/scale"]), m["eps"])
    q = _mm(h, w["layers/attn/wq/w"], precision) + f32(w["layers/attn/wq/b"])
    k = _mm(h, w["layers/attn/wk/w"], precision) + f32(w["layers/attn/wk/b"])
    v = _mm(h, w["layers/attn/wv/w"], precision) + f32(w["layers/attn/wv/b"])
    q = _rope(q.reshape(b, s, m["h"], m["hd"]), positions, m["theta"])
    k = _rope(k.reshape(b, s, m["kv"], m["hd"]), positions, m["theta"])
    v = v.reshape(b, s, m["kv"], m["hd"])
    rep = m["h"] // m["kv"]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) / np.sqrt(m["hd"])
    causal = positions[:, None] >= positions[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision=HIGHEST)
    x = x + _mm(att.reshape(b, s, m["h"] * m["hd"]), w["layers/attn/wo/w"], precision)
    h2 = _rms(x, f32(w["layers/norm2/scale"]), m["eps"])
    gate = _mm(h2, w["layers/mlp/w_gate/w"], precision)
    up = _mm(h2, w["layers/mlp/w_up/w"], precision)
    return x + _mm(jax.nn.silu(gate) * up, w["layers/mlp/w_down/w"], precision)


def hidden(cfg: dict, W: dict, tokens, precision: str = "f32", remat: bool = False):
    """Final-norm hidden states (B, S, d) float32 of ``tokens`` (B, S)."""
    m = dims(cfg)
    x = W["embed/table"].astype(jnp.float32)[tokens]
    positions = jnp.arange(tokens.shape[1])
    stacked = {n: a for n, a in W.items() if n.startswith("layers/")}

    def body(x, w):
        return _layer(m, precision, x, w, positions), None

    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, stacked)
    return _rms(x, W["final_norm/scale"].astype(jnp.float32), m["eps"])


def unembed(W: dict, h, precision: str = "f32"):
    """Logits (..., V) float32 of hidden states ``h`` (..., d)."""
    table = W["embed/table"]
    if precision == "fp8":
        return jnp.matmul(_qdq(h.astype(jnp.float32)), _qdq(table.astype(jnp.float32)).T,
                          precision=HIGHEST)
    return jnp.matmul(h.astype(jnp.float32), table.astype(jnp.float32).T, precision=HIGHEST)


@functools.partial(jax.jit, static_argnames=("cfg_items", "precision"))
def _scored_logits(W, tokens, positions, *, cfg_items, precision):
    cfg = dict(cfg_items)
    h = hidden(cfg, W, tokens[None], precision)[0]
    return unembed(W, h[positions], precision)


def scored_logits(cfg: dict, W: dict, tokens, positions, precision: str = "f32"):
    """Logits (P, V) at ``positions`` of one sequence ``tokens`` (S,)."""
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float))))
    return _scored_logits(W, jnp.asarray(tokens), jnp.asarray(positions),
                          cfg_items=items, precision=precision)


def nll_sum(cfg: dict, W: dict, tokens, labels, mask, precision: str = "f32",
            chunk: int = 512):
    """Summed next-token negative log-likelihood over masked tokens of
    rows ``tokens`` (B, S), and the count of those tokens."""
    h = hidden(cfg, W, tokens, precision, remat=True)
    b, s, d = h.shape
    chunk = min(chunk, s)
    n = s // chunk
    hc = h.reshape(b, n, chunk, d).swapaxes(0, 1)
    lc = labels.reshape(b, n, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, n, chunk).swapaxes(0, 1)

    @jax.checkpoint
    def body(tot, inp):
        hh, ll, mm = inp
        logits = unembed(W, hh, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, ll[..., None], axis=-1)[..., 0]
        return tot + jnp.sum((lse - gold) * mm), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hc, lc, mc))
    return tot, jnp.sum(mask)
