"""Model zoo: one uniform interface over every assigned architecture.

    model = build(cfg)
    params = model.init(key)                           # f32 master weights
    params = init_serving_params(model, key)           # serving: cfg.dtype
    loss, metrics = model.loss(params, batch)          # training
    logits, cache, _ = model.prefill(params, tokens)   # serving
    logits, cache = model.decode_step(params, cache, token)

`batch` dict: tokens (B,S) int32, labels (B,S) int32, mask (B,S) f32,
plus `frames` / `patches` (B, n_frontend_tokens, d) for the stubbed
audio/vision frontends.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer

Params = dict


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: Any
    init: Callable[[jax.Array], Params]
    loss: Callable[[Params, dict], tuple[jax.Array, dict]]
    prefill: Callable[..., tuple]
    decode_step: Callable[[Params, dict, jax.Array], tuple]
    init_cache: Callable[..., dict]
    # paged-kernel decode: (params, kernel_view, token) ->
    # (logits, rows_k, rows_v); None when the family can't run it
    # (SSM/hybrid recurrent state, enc-dec cross caches)
    decode_step_paged: Callable[[Params, dict, jax.Array], tuple] | None = None
    # speculative verify: (params, cache, tokens (B,S), n_new (B,)) ->
    # (logits (B,S,V), cache) — one batched forward scoring a whole
    # draft chunk, bitwise the sequential decode (serve/spec.py); same
    # family gate as the paged decode
    verify_step: Callable[[Params, dict, jax.Array, jax.Array], tuple] | None = None


def _frontend_key(cfg) -> str | None:
    return {"audio": "frames", "vision": "patches"}.get(cfg.frontend) if cfg.frontend else None


def build(cfg) -> Model:
    if cfg.family == "encdec":
        return _build_encdec(cfg)
    return _build_lm(cfg)


# the leaves every forward reads through `.astype(cfg.dtype)`: linear
# weights and biases (`layers.linear`) and embedding tables
# (`layers.embed`/`unembed`). Norm scales and SSM/MoE extras are read
# in f32 and keep their dtype.
_SERVING_CAST_KEYS = frozenset({"w", "b", "table"})


def init_serving_params(model: Model, key: jax.Array) -> Params:
    """Weights for serving: `model.init` with every matrix, bias and
    embedding table held in ``cfg.dtype``, in one jitted program, so a
    float32 copy of the whole model never lives on the device. The
    forward casts those leaves to ``cfg.dtype`` where it reads them, so
    the outputs are bitwise those of ``jax.jit(model.init)(key)``'s f32
    parameters. Training keeps `model.init`'s f32 master weights."""
    dtype = model.cfg.dtype

    def init(k):
        return jax.tree_util.tree_map_with_path(
            lambda path, x: x.astype(dtype) if path[-1].key in _SERVING_CAST_KEYS else x,
            model.init(k),
        )

    return jax.jit(init)(key)


def _build_lm(cfg) -> Model:
    fkey = _frontend_key(cfg)

    def init(key):
        return transformer.init_lm(cfg, key)

    def loss(params, batch):
        extra = batch.get(fkey) if fkey else None
        hidden, aux, _, _ = transformer.forward_lm(
            cfg, params, batch["tokens"], extra_embeds=extra
        )
        labels, mask = batch["labels"], batch["mask"]
        if extra is not None:
            # frontend positions carry no next-token loss
            hidden = hidden[:, extra.shape[1] :]
        ce = transformer.chunked_softmax_xent(cfg, params, hidden, labels, mask)
        total = ce + 0.01 * aux.get("aux_loss", 0.0)
        metrics = {"ce": ce, **aux}
        return total, metrics

    def init_cache(batch_size, max_len, **kw):
        return transformer.init_cache(cfg, batch_size, max_len)

    def prefill(params, tokens, cache=None, length=None, **kw):
        extra = kw.get(fkey) if fkey else None
        if cache is None:
            # frontend tokens (patches/frames) occupy cache slots too
            n_extra = extra.shape[1] if extra is not None else 0
            cache = init_cache(tokens.shape[0], tokens.shape[1] + n_extra)
        return transformer.prefill_lm(
            cfg, params, tokens, cache, extra_embeds=extra, length=length
        )

    def decode_step(params, cache, token):
        return transformer.decode_step_lm(cfg, params, cache, token)

    decode_step_paged = None
    verify_step = None
    if cfg.family != "ssm" and not cfg.hybrid:
        def decode_step_paged(params, pview, token):
            return transformer.decode_step_paged_lm(cfg, params, pview, token)

        def verify_step(params, cache, tokens, n_new):
            return transformer.verify_step_lm(cfg, params, cache, tokens, n_new)

    return Model(cfg, init, loss, prefill, decode_step, init_cache,
                 decode_step_paged, verify_step)


def _build_encdec(cfg) -> Model:
    def init(key):
        return encdec.init_encdec(cfg, key)

    def loss(params, batch):
        ce = encdec.encdec_loss(
            cfg, params, batch["frames"], batch["tokens"], batch["labels"], batch["mask"]
        )
        return ce, {"ce": ce}

    def init_cache(batch_size, max_len, n_frames=None, **kw):
        return encdec.init_encdec_cache(
            cfg, batch_size, max_len, n_frames or cfg.n_frontend_tokens
        )

    def prefill(params, tokens, cache=None, frames=None, **kw):
        b = tokens.shape[0]
        if cache is None:
            cache = init_cache(b, tokens.shape[1] + 64)
        memory = encdec.encode(cfg, params, frames)
        cache = encdec.prime_cross_cache(cfg, params, memory, cache)
        # teacher-forced prefill fills the decoder self-attn cache
        hidden, kv = encdec.decode_train(cfg, params, tokens, memory, want_kv=True)
        kf, vf = kv
        cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], kf.astype(cache["k"].dtype), (0, 0, 0, 0))
        cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vf.astype(cache["v"].dtype), (0, 0, 0, 0))
        logits = transformer.lm_logits(cfg, params, hidden[:, -1:])
        cache["pos"] = jnp.full((), tokens.shape[1], jnp.int32)
        return logits, cache, {}

    def decode_step(params, cache, token):
        return encdec.decode_step_encdec(cfg, params, cache, token)

    return Model(cfg, init, loss, prefill, decode_step, init_cache)


def synthetic_batch(cfg, batch: int, seq: int, key=None) -> dict:
    """Random batch with the right structure (smoke tests / examples)."""
    key = key if key is not None else jax.random.PRNGKey(0)
    k1, k2, k3 = jax.random.split(key, 3)
    out = {
        "tokens": jax.random.randint(k1, (batch, seq), 0, cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(k2, (batch, seq), 0, cfg.vocab_size, jnp.int32),
        "mask": jnp.ones((batch, seq), jnp.float32),
    }
    fkey = _frontend_key(cfg)
    if fkey:
        out[fkey] = (
            jax.random.normal(k3, (batch, cfg.n_frontend_tokens, cfg.d_model), jnp.float32)
            * 0.02
        )
    return out
