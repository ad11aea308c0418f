"""Per-architecture smoke tests (deliverable f): reduced same-family
configs, one forward/train step on CPU, asserting output shapes and no
NaNs. The FULL configs are exercised only via the dry-run."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCH_NAMES, cells, get, get_smoke
from repro.models import build, init_serving_params, synthetic_batch
from repro.train.optimizer import OptConfig, apply_updates, init_opt_state


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_forward_and_train_step(name):
    cfg = get_smoke(name)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2, 32)

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(model.loss, has_aux=True)
    )(params, batch)
    assert loss.shape == ()
    assert jnp.isfinite(loss), f"{name}: loss={loss}"
    for leaf in jax.tree.leaves(grads):
        assert jnp.all(jnp.isfinite(leaf)), f"{name}: non-finite grad"

    opt_cfg = OptConfig(lr=1e-3)
    state = init_opt_state(opt_cfg, params)
    new_params, _ = apply_updates(opt_cfg, params, grads, state)
    for leaf in jax.tree.leaves(new_params):
        assert jnp.all(jnp.isfinite(leaf)), f"{name}: non-finite param"


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_smoke_decode(name):
    cfg = get_smoke(name)
    if not cfg.supports_decode:
        pytest.skip("no decode step")
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 2, 8)
    kw = {}
    if cfg.frontend == "audio":
        kw["frames"] = batch["frames"]
    if cfg.frontend == "vision":
        kw["patches"] = batch["patches"]
    if cfg.family == "ssm":
        cache = model.init_cache(2, 16)
        logits, cache = model.decode_step(params, cache, batch["tokens"][:, :1])
    else:
        cache = model.init_cache(2, 32)
        logits, cache, _ = model.prefill(params, batch["tokens"], cache, **kw)
    assert logits.shape[-1] == cfg.vocab_size
    for _ in range(2):
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        logits, cache = model.decode_step(params, cache, tok)
        assert jnp.all(jnp.isfinite(logits)), name


@pytest.mark.parametrize("name", ["qwen2.5-3b", "hymba-1.5b", "whisper-small"])
def test_serving_params_cast_only_what_the_forward_casts(name):
    """Matrices, biases and embedding tables come out in cfg.dtype, the
    rest keeps the init dtype, and the served logits are bitwise those
    of the f32 parameters the same jitted init produces."""
    cfg = get_smoke(name)
    model = build(cfg)
    key = jax.random.PRNGKey(0)
    p32 = jax.jit(model.init)(key)
    p16 = init_serving_params(model, key)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(p32),
                            jax.tree.leaves(p16)):
        cast = path[-1].key in ("w", "b", "table")
        assert b.dtype == (cfg.dtype if cast else a.dtype), jax.tree_util.keystr(path)
        assert (a.astype(b.dtype) == b).all(), jax.tree_util.keystr(path)
    batch = synthetic_batch(cfg, 2, 8)
    kw = {k: batch[k] for k in ("frames", "patches") if k in batch}
    want = model.prefill(p32, batch["tokens"], **kw)[0]
    got = model.prefill(p16, batch["tokens"], **kw)[0]
    assert (want == got).all(), name


def test_full_configs_match_assignment():
    """Pin the exact assigned hyperparameters."""
    spec = {
        "hymba-1.5b": (32, 1600, 25, 5, 5504, 32001),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "qwen1.5-0.5b": (24, 1024, 16, 16, 2816, 151936),
        "starcoder2-15b": (40, 6144, 48, 4, 24576, 49152),
        "qwen2.5-3b": (36, 2048, 16, 2, 11008, 151936),
        "whisper-small": (12, 768, 12, 12, 3072, 51865),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "pixtral-12b": (40, 5120, 32, 8, 14336, 131072),
        "mamba2-130m": (24, 768, 0, 0, 0, 50280),
    }
    for name, (L, d, h, kv, ff, v) in spec.items():
        cfg = get(name)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                cfg.d_ff, cfg.vocab_size) == (L, d, h, kv, ff, v), name
    assert get("hymba-1.5b").ssm_state == 16
    assert get("mamba2-130m").ssm_state == 128
    assert get("mixtral-8x7b").n_experts == 8
    assert get("mixtral-8x7b").experts_per_token == 2
    assert get("llama4-scout-17b-a16e").n_experts == 16
    assert get("llama4-scout-17b-a16e").experts_per_token == 1


def test_cell_grid_counts():
    all_cells = cells(include_skips=True)
    assert len(all_cells) == 40
    skipped = [c for c in all_cells if c[2]]
    # 5 pure-full-attention archs + whisper skip long_500k
    assert len(skipped) == 6
    for arch, shape, reason in skipped:
        assert shape == "long_500k"


def test_param_counts_plausible():
    """Analytic param counts should land near the nameplate sizes."""
    expect = {
        "tinyllama-1.1b": (0.9e9, 1.4e9),
        "qwen1.5-0.5b": (0.3e9, 0.7e9),
        "qwen2.5-3b": (2.0e9, 3.6e9),
        "starcoder2-15b": (13e9, 17e9),
        "mixtral-8x7b": (42e9, 50e9),
        "mamba2-130m": (0.09e9, 0.2e9),
        "hymba-1.5b": (1.0e9, 2.1e9),
        "pixtral-12b": (10e9, 14e9),
    }
    for name, (lo, hi) in expect.items():
        n = get(name).param_count()
        assert lo < n < hi, f"{name}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"
