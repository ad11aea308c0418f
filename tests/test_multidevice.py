"""Multi-device integration tests (8 fake CPU devices, subprocesses):
stream machinery, decoupled-vs-conventional equivalence, the three
paper case-study apps, elastic restart."""
import pytest

pytestmark = pytest.mark.slow


def test_stream_reduce_roundtrip(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core import GroupedMesh, make_channel, stream_reduce, stream_reduce_and_return
from repro.utils.compat import make_mesh, shard_map
mesh = make_mesh((8,), ("data",))
gm = GroupedMesh.build(mesh, services={"reduce": 2/8})
ch = make_channel(gm, "reduce")
def f(x):
    red = stream_reduce(x[0], ch)
    back = stream_reduce_and_return(x[0], ch, transform=lambda r: r * 2.0)
    return red[None], back[None]
sf = jax.jit(shard_map(f, mesh, P("data"), (P("data"), P("data"))))
x = jnp.asarray(np.random.default_rng(0).normal(size=(8, 4, 16)).astype(np.float32))
red, back = sf(x)
expected = np.asarray(x[:6].sum(0))
np.testing.assert_allclose(np.asarray(red[6]), expected, rtol=1e-5, atol=1e-5)
for r in range(8):
    np.testing.assert_allclose(np.asarray(back[r]), 2*expected, rtol=1e-4, atol=1e-4)
print("OK")
""")


def test_decoupled_equals_conventional_grads(multidevice):
    multidevice("""
import jax, jax.numpy as jnp, numpy as np, dataclasses
from repro.configs import get_smoke
from repro.models import build, synthetic_batch
from repro.train.optimizer import OptConfig, init_opt_state
from repro.train.train_step import TrainStepConfig, make_jitted_step
from repro.utils.compat import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))
cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"), dtype=jnp.float32)
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
opt_cfg = OptConfig(kind="sgdm", lr=1.0, beta1=0.0, warmup_steps=0, grad_clip=0.0,
                    weight_decay=0.0, min_lr_ratio=1.0, total_steps=1)
opt_state = init_opt_state(opt_cfg, params)
batch = synthetic_batch(cfg, 8, 32)
mask = np.asarray(batch["mask"]).copy(); mask[6:] = 0.0
batch["mask"] = jnp.asarray(mask)
params_like = jax.eval_shape(lambda: params)
outs = {}
with jax.set_mesh(mesh):
    for name, kw in [("conventional", dict(mode="conventional")),
                     ("overlap", dict(mode="overlap")),
                     ("decoupled", dict(mode="decoupled", reduce_alpha=0.25)),
                     ("decoupled_int8", dict(mode="decoupled", reduce_alpha=0.25, compress="int8"))]:
        step, _ = make_jitted_step(model, mesh, opt_cfg, TrainStepConfig(**kw), params_like, batch, donate=False)
        outs[name] = step(params, opt_state, batch)[0]
ref = jax.tree.leaves(outs["conventional"])
for name, tol in [("overlap", 1e-5), ("decoupled", 1e-5), ("decoupled_int8", 0.02)]:
    d = max(float(jnp.max(jnp.abs(a-b))) for a, b in zip(ref, jax.tree.leaves(outs[name])))
    assert d < tol, (name, d)
print("OK")
""")


def test_mapreduce_equivalence(multidevice):
    multidevice("""
import numpy as np
from repro.apps.mapreduce import CorpusCfg, run_wordcount
from repro.utils.compat import make_mesh
mesh = make_mesh((8,), ("data",))
cfg = CorpusCfg(n_docs_per_row=4, words_per_doc=256, vocab=500, skew=0.7)
h_ref, _ = run_wordcount(mesh, "reference", cfg)
h_dec, _ = run_wordcount(mesh, "decoupled", cfg, alpha=0.25)
assert np.abs(h_ref - h_dec).max() < 1e-3, np.abs(h_ref - h_dec).max()
assert h_ref.sum() > 0
print("OK")
""")


def test_cg_variants_agree(multidevice):
    multidevice("""
import numpy as np, dataclasses
from repro.apps.cg import CGCfg, run_cg
from repro.utils.compat import make_mesh
mesh = make_mesh((8,), ("data",))
base = CGCfg(nx_local=14, ny=12, nz=12, n_iters=20)
hists = {}
for mode in ["blocking", "nonblocking", "decoupled"]:
    cfg = dataclasses.replace(base, mode=mode)
    u, res, hist = run_cg(mesh, cfg, alpha=0.125)
    hists[mode] = np.sqrt(hist)
    assert hist[-1] < hist[0], mode  # converging
for m in ["nonblocking", "decoupled"]:
    d = np.max(np.abs(hists[m] - hists["blocking"]) / hists["blocking"])
    assert d < 1e-3, (m, d)
print("OK")
""")


def test_pic_conservation_and_ownership(multidevice):
    multidevice("""
import numpy as np
from repro.apps.pic import PICCfg, run_pic
from repro.utils.compat import make_mesh
mesh = make_mesh((8,), ("data",))
cfg = PICCfg(capacity=1024, n_particles_total=1024, n_steps=3, dt=0.15)
for mode, rows, alpha in [("reference", 8, 0.0), ("decoupled", 7, 0.125)]:
    x, v, m, counts = run_pic(mesh, mode, cfg, alpha=alpha or 0.125)
    assert m.sum() == 1024, (mode, m.sum())        # conservation
    width = cfg.domain / rows
    for r in range(rows):                           # ownership
        owner = np.floor(x[r][m[r] > 0] / width).astype(int)
        assert (owner == r).all(), (mode, r)
print("OK")
""")


def test_disagg_spmd_kv_handoff(multidevice):
    """Disaggregated serving tick on the grouped mesh: prefill rows
    stream their KV caches through the channel into decode slots, and
    the decode rows' state matches a host-side replay bit-for-bit."""
    multidevice("""
import dataclasses, jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke
from repro.models import build
from repro.utils.compat import make_mesh
from repro.core.operators import migrate_cache_into_slot
from repro.serve.disagg import (serving_mesh, build_disagg_spmd_step,
                                init_disagg_state, kv_handoff_channel)

cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"), dtype=jnp.float32)
model = build(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = make_mesh((8,), ("data",))
gm = serving_mesh(mesh, alpha=2/8)          # rows 6,7 prefill; 0..5 decode
ch = kv_handoff_channel(gm)
assert ch.n_waves == 1 and ch.wave_perm(0) == [(6, 0), (7, 1)]
MAX_PROMPT, SLOTS, MAX_LEN, STEPS = 8, 2, 32, 2
step, plan = build_disagg_spmd_step(model, gm, max_prompt=MAX_PROMPT,
    slots_per_row=SLOTS, max_len=MAX_LEN, chunk_elems=1024, decode_steps=STEPS)
cache, tokens = init_disagg_state(model, gm, slots_per_row=SLOTS, max_len=MAX_LEN)

rng = np.random.default_rng(0)
prompts = np.zeros((8, MAX_PROMPT), np.int32)
plen = np.zeros((8,), np.int32)
p6 = rng.integers(0, cfg.vocab_size, 3).astype(np.int32)
p7 = rng.integers(0, cfg.vocab_size, 5).astype(np.int32)
prompts[6, :3] = p6; plen[6] = 3
prompts[7, :5] = p7; plen[7] = 5
dst = -np.ones((8, ch.n_waves), np.int32)
dst[0, 0] = 0; dst[1, 0] = 1
cache, tokens, out, stats = step(params, jnp.asarray(prompts), jnp.asarray(plen),
                                 jnp.asarray(dst), cache, tokens)
assert list(np.asarray(stats)[0]) == [2, 6 * SLOTS * STEPS], np.asarray(stats)[0]
for row, prompt, slot in [(0, p6, 0), (1, p7, 1)]:
    # host replay: exact-length prefill, local migration, STEPS decodes
    logits, c1, _ = model.prefill(params, jnp.asarray(prompt)[None, :])
    first = int(jnp.argmax(logits[0, -1]))
    full = migrate_cache_into_slot(model.init_cache(SLOTS, MAX_LEN), c1, slot)
    t = jnp.zeros((SLOTS, 1), jnp.int32).at[slot, 0].set(first)
    toks = []
    for _ in range(STEPS):
        lg, full = model.decode_step(params, full, t)
        t = jnp.argmax(lg[:, -1], axis=-1).astype(jnp.int32)[:, None]
        toks.append(int(t[slot, 0]))
    b = row * SLOTS + slot
    assert list(np.asarray(out)[b]) == toks, (row, np.asarray(out)[b], toks)
    np.testing.assert_array_equal(
        np.asarray(cache["k"])[:, row * SLOTS:(row + 1) * SLOTS],
        np.asarray(full["k"]))
    assert int(np.asarray(cache["pos"])[row]) == int(full["pos"])
print("OK")
""")


def test_trainer_crash_resume_and_elastic(multidevice):
    multidevice("""
import shutil, jax, numpy as np
from repro.utils.compat import make_mesh
from repro.configs import get_smoke
from repro.models import build
from repro.data.pipeline import Pipeline, DataConfig
from repro.train.optimizer import OptConfig
from repro.train.train_step import TrainStepConfig
from repro.train.trainer import Trainer, TrainerConfig, SimulatedFailure

ckdir = "/tmp/repro_test_ckpt_resume"; shutil.rmtree(ckdir, ignore_errors=True)
cfg = get_smoke("qwen2.5-3b"); model = build(cfg)
pipe = Pipeline(DataConfig(vocab_size=cfg.vocab_size, seq_len=16, global_batch=8))
opt = OptConfig(lr=1e-3, warmup_steps=2, total_steps=20)

mesh = make_mesh((4, 2), ("data", "model"))
with jax.set_mesh(mesh):
    tr = Trainer(model, mesh, pipe, opt, TrainStepConfig(mode="decoupled", reduce_alpha=0.25),
                 TrainerConfig(total_steps=8, ckpt_every=3, ckpt_dir=ckdir, log_every=100, fail_at_step=5))
    try:
        tr.run(); raise SystemExit("expected failure")
    except SimulatedFailure:
        pass
    tr.close()

# elastic: resume the SAME checkpoint on a DIFFERENT mesh shape
mesh2 = make_mesh((2, 4), ("data", "model"))
with jax.set_mesh(mesh2):
    tr2 = Trainer(model, mesh2, pipe, opt, TrainStepConfig(mode="conventional"),
                  TrainerConfig(total_steps=8, ckpt_every=3, ckpt_dir=ckdir, log_every=100))
    state = tr2.run(); tr2.close()
assert state["step"] == 8
print("OK")
""")
