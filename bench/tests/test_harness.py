"""The harness finds every cell's files by name, refuses to run where it
cannot measure, keeps its peaks table to known chips, takes each
architecture from files of its own, and counts the Qwen2 cells as it
always has."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys
import types
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench import common  # noqa: E402


def bench_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_cell_finds_its_files():
    bj = bench_json()
    configs = {c["name"]: c for c in bj["configs"]}
    for wl in bj["workloads"]:
        assert (ROOT / configs[wl["config"]]["file"]).is_file()
        assert (ROOT / "bench" / "traffic" / f"{wl['traffic']}.json").is_file()
        cell = json.loads((ROOT / "bench" / "cells" / f"{wl['name']}.json").read_text())
        assert (ROOT / "bench" / "drivers" / f"{cell['driver']}.py").is_file()
    for m in bj["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file(), m["name"]


def test_peaks_refuse_an_unknown_chip():
    assert common.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        common.peaks("cpu")


def _run(cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen2.5-3b.decode-closed32",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300, env=env, cwd=cwd)


def test_no_tpu_means_no_result():
    proc = _run(ROOT)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run(tmp_path)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout


def test_traffic_is_the_seed_and_only_the_seed():
    """The same seed gives the same requests; another seed gives other
    requests but, over any 100 of them, the same amount of work."""
    from bench import traffic

    mix = json.loads((ROOT / "bench" / "traffic" / "decode-closed32.json").read_text())
    a = traffic.closed_loop_requests(mix, 1000, 3_000_000_019)
    b = traffic.closed_loop_requests(mix, 1000, 3_000_000_019)
    c = traffic.closed_loop_requests(mix, 1000, 2**33 + 5)
    assert all((x.prompt == y.prompt).all() and x.max_new_tokens == y.max_new_tokens
               for x, y in zip(a, b))
    assert [len(r.prompt) for r in a[:100]] != [len(r.prompt) for r in c[:100]]
    for lo in range(mix["clients"], len(a) - 100, 100):
        for field in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
            wa = sum(map(field, a[lo:lo + 100]))
            wc = sum(map(field, c[lo:lo + 100]))
            assert abs(wa - wc) < 0.05 * wa
    lens = [len(r.prompt) for r in a]
    assert min(lens) == mix["prompt"]["min"] and max(lens) == mix["prompt"]["max"]
    assert all(n % mix["prompt"]["quantum"] == 0 for n in lens)


# The Qwen2 configuration files' yardstick, pinned: FLOPs (bench/flops.py)
# at ATTENDED positions, prompts of 64, 700 and 2,048 tokens and rows of
# 128 and 2,048; paged attention's (FLOPs, bytes) over ATTENDED at 2 and 1
# bytes an element; and sha256 prefixes of weight leaves drawn at SEED at
# TINY_WIDTHS, by the program's draw and by the reference's.
ATTENDED = [1, 255, 700, 2047, 4095]
SEED = 3_000_000_019
TINY_WIDTHS = {"hidden_size": 128, "intermediate_size": 256, "num_attention_heads": 4,
               "num_key_value_heads": 2, "vocab_size": 512, "num_hidden_layers": 2}
GOLDEN = {
    "qwen2.5-3b": {
        "decode_token": [6171688960.0, 6246596608.0, 6377832448.0, 6775078912.0, 7379058688.0],
        "prefill": [356375855104.0, 3957323923456.0, 11983883075584.0],
        "train_token": [18571247616.0, 19420594176.0],
        "paged_attention": [(2093285376.0, 263135232.0), (2093285376.0, 132304896.0)],
        "weights": {"embed/table": "7f8359dd84df80c6", "final_norm/scale": "aadb6eb15775f24c",
                    "layers/attn/wq/b": "676b3e4b8a5a16c5",
                    "layers/mlp/w_down/w": "bfc4cdfcb0c3ab4c"},
    },
    "qwen1.5-0.5b": {
        "decode_token": [773660672.0, 792387584.0, 825196544.0, 924508160.0, 1075503104.0],
        "prefill": [30059528192.0, 342095740928.0, 1102045773824.0],
        "train_token": [2335027200.0, 2547363840.0],
        "paged_attention": [(523321344.0, 523689984.0), (523321344.0, 262029312.0)],
        "weights": {"embed/table": "7e3bc790080e61ca", "final_norm/scale": "aadb6eb15775f24c",
                    "layers/attn/wq/b": "80c004148455d4f3",
                    "layers/mlp/w_down/w": "f9ea8270c4749bbe"},
    },
}


def _sha(x) -> str:
    import numpy as np

    return hashlib.sha256(np.asarray(x).tobytes()).hexdigest()[:16]


@pytest.mark.parametrize("name", sorted(GOLDEN))
def test_qwen2_numbers_are_unchanged(name):
    import jax
    import jax.numpy as jnp

    from bench import arch, flops, program, weights
    from bench.kernels import paged_attention as pa

    cfg = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    g = GOLDEN[name]
    assert [flops.decode_token(cfg, a) for a in ATTENDED] == g["decode_token"]
    assert [flops.prefill(cfg, n) for n in (64, 700, 2048)] == g["prefill"]
    assert [flops.train_token(cfg, s) for s in (128, 2048)] == g["train_token"]
    assert [pa.cost(cfg, ATTENDED), pa.cost(cfg, ATTENDED, kv_bytes=1)] == g["paged_attention"]

    tiny = dict(cfg, **TINY_WIDTHS)
    plugin = arch.load(tiny)
    stored = program.stored_dtype(tiny)
    tree = {n: jax.ShapeDtypeStruct(s, jnp.float32)
            for n, s in plugin.reference.leaf_shapes(tiny).items()}
    params = weights.make_params(tree, SEED, stored, arch=plugin.module)
    ref = plugin.reference.make_weights(tiny, SEED, stored)
    assert {n: _sha(params[n]) for n in g["weights"]} == g["weights"]
    assert {n: _sha(ref[n]) for n in g["weights"]} == g["weights"]


# -- an architecture joins as files of its own -----------------------------

TOY = {"name": "toy-1", "model_type": "toy", "hidden_size": 8, "vocab_size": 16,
       "weights_dtype": "float32", "compute_dtype": "bfloat16"}


@pytest.fixture
def toy(monkeypatch):
    """A toy architecture put where ``bench/arch/toy.py`` and
    ``bench/reference/toy.py`` would be: a leaf kind of its own
    (``gain``), a second stacked prefix (``blocks/``), and two layers of
    which only the second attends. Its reference maps each token to its
    logits alone, so greedy tokens score a gap of exactly 0."""
    import jax
    import jax.numpy as jnp

    from bench import weights

    mod = types.ModuleType("bench.arch.toy")
    mod.STACKED = ("layers/", "blocks/")
    mod.INITIALIZERS = {"gain": lambda key, shape: 2.0 + jax.random.uniform(key, shape)}
    mod.arch_config = lambda cfg, compute_dtype: ("toy", cfg["name"], compute_dtype)
    mod.stored_dtype = lambda weights_dtype: (lambda name: jnp.float32)
    mod.decode_token = lambda cfg, attended: 1000.0 + attended
    mod.prefill = lambda cfg, n: 10.0 * n
    mod.train_token = lambda cfg, seq: 7.0 * seq
    mod.attention = lambda cfg: {"layers": [1], "heads": 4, "kv_heads": 2, "head_dim": 8}
    mod.tiny = lambda cfg: dict(cfg, vocab_size=12)

    ref = types.ModuleType("bench.reference.toy")

    def leaf_shapes(cfg):
        d = cfg["hidden_size"]
        return {"embed/table": (cfg["vocab_size"], d), "blocks/mix/gain": (2, d),
                "layers/proj/w": (2, d, d)}

    def make_weights(cfg, seed, stored_dtype):
        @jax.jit  # as the program's copy is drawn: one compiled call
        def build(key):
            return {n: weights.leaf(key, n, s, stored_dtype(n), arch=mod)
                    for n, s in leaf_shapes(cfg).items()}

        return build(weights.base_key(seed))

    def logits(W, tokens):
        x = W["embed/table"][tokens]
        for layer in range(2):
            x = jnp.tanh(x @ W["layers/proj/w"][layer]) * W["blocks/mix/gain"][layer]
        return x @ W["embed/table"].T

    def scored_logits(cfg, W, tokens, positions, precision="f32"):
        return logits(W, jnp.asarray(tokens))[jnp.asarray(positions)]

    def nll_sum(cfg, W, tokens, labels, mask, precision="f32"):
        lg = logits(W, tokens)
        gold = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
        return jnp.sum((jax.nn.logsumexp(lg, axis=-1) - gold) * mask), jnp.sum(mask)

    ref.leaf_shapes, ref.make_weights = leaf_shapes, make_weights
    ref.scored_logits, ref.nll_sum, ref.logits = scored_logits, nll_sum, logits
    monkeypatch.setitem(sys.modules, "bench.arch.toy", mod)
    monkeypatch.setitem(sys.modules, "bench.reference.toy", ref)
    return mod, ref


def test_a_new_architecture_is_reached_through_its_own_files(toy):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from bench import arch, flops, program, rehearse, weights
    from bench.drivers import serve, train
    from bench.kernels import paged_attention as pa

    mod, ref = toy
    assert arch.load(TOY) == (mod, ref)
    assert program.arch_config(TOY, "bfloat16") == ("toy", "toy-1", "bfloat16")
    assert (flops.decode_token(TOY, 5), flops.prefill(TOY, 3), flops.train_token(TOY, 4)) \
        == (1005.0, 30.0, 28.0)
    # one attending layer of two: 4 heads and 2 KV heads of 8
    assert pa.cost(TOY, [10, 20]) == (4.0 * 32 * 30, 2.0 * 16 * 2 * 30 + 2.0 * 2 * 32 * 2)

    # the new kind is drawn by the toy's initializer, layer by layer under
    # its second prefix, and the reference draws the same numbers
    tree = {n: jax.ShapeDtypeStruct(s, jnp.float32) for n, s in ref.leaf_shapes(TOY).items()}
    params = weights.make_params(tree, SEED, program.stored_dtype(TOY), arch=mod)
    gain = np.asarray(params["blocks/mix/gain"])
    assert ((2.0 <= gain) & (gain < 3.0)).all()
    key = weights.base_key(SEED)
    for layer in range(2):
        np.testing.assert_array_equal(gain[layer], weights.leaf(
            key, "blocks/mix/gain", (2, 8), jnp.float32, layer=layer, arch=mod))
    for n, x in ref.make_weights(TOY, SEED, program.stored_dtype(TOY)).items():
        np.testing.assert_array_equal(np.asarray(params[n]), np.asarray(x))

    # the serving check scores with the toy's reference: greedy tokens
    # read a gap of 0, one altered token does not
    W = ref.make_weights(TOY, SEED, program.stored_dtype(TOY))
    prompt = np.array([3, 1, 4, 1], np.int32)
    served = [int(prompt[-1])]
    for _ in range(5):
        served.append(int(jnp.argmax(ref.logits(W, jnp.asarray([served[-1]]))[0])))
    served = served[1:]
    req = types.SimpleNamespace(prompt=prompt, out_tokens=served[1:])
    ctx = types.SimpleNamespace(jax=jax, seed=SEED, control=False)
    cell = {"check": {"requests": 1, "pad_tokens": 16, "pad_positions": 8, "logit_gap": 0.1}}
    checks, readings = serve.check(ctx, TOY, cell, [{"req": req, "first_token": served[0]}])
    assert readings["tokens_scored"] == 5 and checks["logit_gap"]["value"] == 0.0
    req.out_tokens = served[1:-1] + [(served[-1] + 1) % TOY["vocab_size"]]
    checks, _ = serve.check(ctx, TOY, cell, [{"req": req, "first_token": served[0]}])
    assert checks["logit_gap"]["value"] > 0.0

    # the training reference takes its leaves, loss and draws from the toy
    cells = ROOT / "bench" / "cells"
    tcell = json.loads((cells / "qwen1.5-0.5b.train-decoupled4.json").read_text())
    ctx = types.SimpleNamespace(jax=jax, seed=SEED, config=TOY, cell=tcell,
                                mix={"seq": 8, "rows_per_chip": 1})
    out = train.reference_steps(ctx, 4, "f32")
    assert len(out["loss"]) == 3 and all(np.isfinite(out["loss"]))
    assert set(out["change"]) == set(ref.leaf_shapes(TOY))
    assert out["change"]["blocks/mix/gain"] > 0.0

    # the rehearsal cuts the file by the toy's own tiny()
    scell = json.loads((cells / "qwen2.5-3b.decode-closed32.json").read_text())
    mix = json.loads((ROOT / "bench" / "traffic" / "decode-closed32.json").read_text())
    _, config, _ = rehearse.shrink(scell, TOY, mix)
    assert config == dict(TOY, vocab_size=12)


def test_a_missing_architecture_names_the_file_to_add(monkeypatch):
    from bench import arch, flops, program

    with pytest.raises(ModuleNotFoundError, match="add bench/arch/nosuch.py"):
        program.arch_config({"model_type": "nosuch"}, "bfloat16")
    with pytest.raises(ModuleNotFoundError, match="add bench/arch/nosuch.py"):
        flops.decode_token({"model_type": "nosuch"}, 1)
    monkeypatch.setitem(sys.modules, "bench.arch.half", types.ModuleType("bench.arch.half"))
    with pytest.raises(ModuleNotFoundError, match="add bench/reference/half.py"):
        arch.load({"model_type": "half"})
    with pytest.raises(ValueError, match="model_type"):
        arch.load({"hidden_size": 8})


def test_first_token_is_the_requests_own_before_the_prefix_caches():
    """A program that puts its prefill's token on the request is read
    there; one that does not is read from the prefix cache's whole-prompt
    entry, as the Qwen2 cells are."""
    import numpy as np

    from bench.drivers import serve

    prompt = np.array([5, 6, 7], np.int32)
    entry = types.SimpleNamespace(first=9)
    prefix = types.SimpleNamespace(entries={("full", prompt.astype(np.int64).tobytes()): entry})
    loop = types.SimpleNamespace(eng=types.SimpleNamespace(kv=types.SimpleNamespace(prefix=prefix)))
    first = serve.ClosedLoop._first_token
    assert first(loop, types.SimpleNamespace(prompt=prompt)) == 9
    assert first(loop, types.SimpleNamespace(prompt=prompt, first_token=None)) == 9
    assert first(loop, types.SimpleNamespace(prompt=prompt, first_token=np.int32(4))) == 4
    assert first(loop, types.SimpleNamespace(prompt=prompt[:2])) is None
    no_cache = types.SimpleNamespace(eng=types.SimpleNamespace(kv=types.SimpleNamespace()))
    assert first(no_cache, types.SimpleNamespace(prompt=prompt)) is None
    assert first(no_cache, types.SimpleNamespace(prompt=prompt, first_token=4)) == 4
