"""Compile the main-path Pallas kernels and programs for a described
TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, Mosaic calls GSPMD would have to partition,
programs larger than HBM), so these compiles guard the chip path from
the CPU. Nothing runs: each test lowers shapes and asserts the compiled
program holds the kernel (``tpu_custom_call``) or fits the chip.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests and only the worker
that runs this file loads the TPU library.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.paged_attention.paged_attention import paged_decode_attention_kernel
from repro.kernels.runtime import ENV_INTERPRET
from repro.kernels.sample.sample import argmax_last_kernel
from repro.kernels.stream_reduce.stream_reduce import chunk_accumulate
from repro.models import build, init_serving_params, synthetic_batch, transformer

HBM_BYTES = 15.75e9  # what XLA lets one v5e program address
# qwen2.5-3b decode widths (configs/qwen2_5_3b.py), 8 slots, 16-token blocks
B, H, N_KV, HD, BS, MAX_LEN = 8, 16, 2, 128, 16, 2048
NB, MB = B * MAX_LEN // BS + 1, MAX_LEN // BS
VOCAB = 151936


@pytest.fixture(scope="module")
def topo():
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")  # the compiler logs nowhere
    # a compile for a described chip cannot be read back from the
    # persistent cache without one: keep it out of the cache
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies

    try:
        yield topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shapes(tree, sharding):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
                        tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


# (slots, pool blocks, block size, table entries): 8 slots of 2,048;
# the serving cell's pool (32 slots of 4,096 in 16-token blocks); the
# dense store's identity table (one 4,096-row block a slot)
PAGED_GEOMETRIES = {"": (B, NB, BS, MB), "cell-": (32, 5121, 16, 256),
                    "dense-": (32, 32, 4096, 1)}


@pytest.mark.parametrize("geometry,pool_dtype", [
    pytest.param(geo, dt, id=geo + name)
    for geo in PAGED_GEOMETRIES for dt, name in ((jnp.bfloat16, "fp"), (jnp.int8, "int8"))
])
def test_paged_attention_compiles(one_chip, geometry, pool_dtype):
    b, nb, bs, mb = PAGED_GEOMETRIES[geometry]
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = [s((b, 1, H, HD), jnp.bfloat16), s((b, N_KV * HD), jnp.bfloat16),
            s((b, N_KV * HD), jnp.bfloat16), s((nb, bs, N_KV * HD), pool_dtype),
            s((nb, bs, N_KV * HD), pool_dtype), s((b, mb), jnp.int32), s((b,), jnp.int32)]
    scales = [s((nb, bs), jnp.float32)] * 2 if pool_dtype == jnp.int8 else []

    def fn(*a):
        k_scale, v_scale = a[7:] or (None, None)
        return paged_decode_attention_kernel(
            *a[:7], n_kv=N_KV, window=0, scale=HD**-0.5,
            k_scale=k_scale, v_scale=v_scale, interpret=False)

    assert "tpu_custom_call" in _compile(fn, *args, *scales).as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_argmax_last_compiles(one_chip, dtype):
    x = jax.ShapeDtypeStruct((B, VOCAB), dtype, sharding=one_chip)
    hlo = _compile(functools.partial(argmax_last_kernel, interpret=False), x).as_text()
    assert "tpu_custom_call" in hlo


def test_chunk_accumulate_compiles(one_chip):
    # the wave fold's (acc, arrived) stack of one 4 MiB f32 wire group
    x = jax.ShapeDtypeStruct((2, 1 << 20), jnp.float32, sharding=one_chip)
    hlo = _compile(functools.partial(chunk_accumulate, interpret=False), x).as_text()
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    q = jax.ShapeDtypeStruct((1, H, 2048, HD), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, N_KV, 2048, HD), jnp.bfloat16, sharding=one_chip)
    hlo = _compile(functools.partial(flash_attention, interpret=False), q, kv, kv).as_text()
    assert "tpu_custom_call" in hlo


def test_qwen2_5_3b_paged_decode_fits_one_chip(one_chip, monkeypatch):
    monkeypatch.setenv(ENV_INTERPRET, "0")
    cfg = get("qwen2.5-3b")
    model = build(cfg)
    params = jax.eval_shape(lambda k: init_serving_params(model, k), jax.random.PRNGKey(0))
    pool = jax.ShapeDtypeStruct((cfg.n_layers, NB, BS, cfg.d_kv), jnp.bfloat16)
    pview = {"k_pool": pool, "v_pool": pool,
             "tables": jax.ShapeDtypeStruct((B, MB), jnp.int32),
             "pos": jax.ShapeDtypeStruct((B,), jnp.int32),
             "rows_like": jax.ShapeDtypeStruct((0,), jnp.bfloat16)}
    token = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    step = functools.partial(transformer.decode_step_paged_lm, cfg, impl="kernel")
    compiled = _compile(step, *_shapes((params, pview, token), one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used <= HBM_BYTES, used
    # the matrices and the embedding are bf16: about 2 bytes a parameter
    assert mem.argument_size_in_bytes < 2.2 * cfg.param_count() + 2 * pool.size * 2


def test_decoupled_train_step_folds_with_kernel(topo, monkeypatch):
    """(4, 1) mesh, chunked gradient stream: the wave fold runs the
    chunk_accumulate kernel, which needs a fully manual region."""
    import repro.kernels.runtime as runtime
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import TrainStepConfig, make_jitted_step

    monkeypatch.setattr(runtime, "on_tpu", lambda: True)
    monkeypatch.setenv(ENV_INTERPRET, "0")
    mesh = Mesh(np.asarray(topo.devices[:4]).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    cfg = dataclasses.replace(get("qwen1.5-0.5b"), n_layers=1, dtype=jnp.float32)
    model = build(cfg)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    opt_cfg = OptConfig(kind="sgdm", lr=1.0, beta1=0.0)
    opt = jax.eval_shape(lambda p: init_opt_state(opt_cfg, p), params)
    batch = jax.eval_shape(lambda: synthetic_batch(cfg, 8, 128))
    ts = TrainStepConfig(mode="decoupled", reduce_alpha=0.25, wire_chunk_bytes=4 << 20)
    with jax.set_mesh(mesh):
        step, in_sh = make_jitted_step(model, mesh, opt_cfg, ts, params, batch, donate=False)
        args = jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s),
            (params, opt, batch), in_sh)
        hlo = step.lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
