#!/usr/bin/env python3
"""Smoke run of the system's main paths on TPU, in one process.

No arguments (one chip): full-width qwen2.5-3b (36 layers, d_model
2048, 16 heads / 2 KV heads, d_ff 11008, vocab 151936; random weights
from --seed) served through the user entry points
``configs.get -> models.build -> serve.make_engine`` with continuous
batching and the paged KV store with its prefix cache: 8 requests with
prompts of 128-1024 tokens, 32 new tokens each. It then checks that the
compiled decode and sampling programs hold the Pallas kernels
(``tpu_custom_call``), not the jnp references, and teacher-forces a few
decode steps through the kernel and the reference attention on the chip
and compares their logits.

``--chips 4``: only the paper's decoupled groups on a four-chip mesh.
The MapReduce word histogram, reference against decoupled (one reduce
chip), must be bit-identical; the decoupled train step with the chunked
gradient stream (folded by the ``chunk_accumulate`` kernel) must match
the conventional step at qwen1.5-0.5b widths.

Every phase raises on failure, so the script exits non-zero and prints
no result line. The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

Run:  python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import configs  # noqa: E402
from repro.kernels.runtime import resolve_interpret  # noqa: E402
from repro.kernels.sample import sample_last  # noqa: E402
from repro.models import build, init_serving_params, synthetic_batch, transformer  # noqa: E402
from repro.serve import EngineConfig, KVSpec, Request, make_engine  # noqa: E402
from repro.serve.engine import PrefillRunner  # noqa: E402
from repro.serve.kvstore import make_kvstore  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

SERVE_MODEL = "qwen2.5-3b"
N_REQUESTS = 8
PROMPT_LENS = (128, 1024)  # inclusive range of prompt lengths
SHARED_PREFIX = 128  # a system prompt shared by the first half of the requests
NEW_TOKENS = 32
MAX_LEN = 2048
BLOCK = 16
TEACHER_STEPS = 4
# The reference attention (layers.attention_decode) rounds scores,
# probabilities and its output to bf16, 8 significant bits each; the
# kernel keeps them in f32 and rounds only its output. Over 36 residual
# layers of random weights that difference reached 1.3-1.9 % of the
# largest |logit| in a CPU interpret-mode run at d_model 512 and depths
# 4-36, and a kernel that reads the wrong KV head gives ~70 %. 5 % sits
# between the two. Off-by-one masks move the logits by only ~3 %: the
# interpret-mode tests pin those at 2e-5 on f32 inputs.
LOGIT_TOL = 0.05

TRAIN_MODEL = "qwen1.5-0.5b"
TRAIN_LAYERS = 4  # depth cut; widths are the published ones
TRAIN_BATCH, TRAIN_SEQ = 8, 1024
WIRE_CHUNK_BYTES = 4 << 20
# Compute in f32 as tests/test_multidevice.py does, with f32 matmuls at
# full precision: the two steps then differ only in the order the
# per-row gradients are summed (all-reduce against the chunked stream
# fold). At the TPU's default precision, f32 matmuls round their inputs
# to bf16 and the two programs fuse differently: that moved single
# updates by 5.7e-3 of the largest on four v5e chips. A lost or doubled
# wave moves them by tens of %. Measured as the largest update
# difference over the largest update (SGD, lr 1: the update is the
# gradient).
UPDATE_TOL = 1e-3


class _CompileLog:
    """Counts backend compiles (persistent-cache loads included) and
    their seconds through jax.monitoring."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += secs

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def _check(ok, what: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def _require_kernel(hlo: str, program: str) -> None:
    _check("tpu_custom_call" in hlo, f"compiled {program} holds a Pallas kernel")


def _require_tpu(chips: int) -> jax.Device:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"no TPU: JAX's first device is {dev.platform!r}")
    if len(jax.devices()) != chips:
        raise SystemExit(f"expected {chips} chips, JAX sees {len(jax.devices())}")
    if resolve_interpret(None):
        raise SystemExit("REPRO_KERNEL_INTERPRET forces the Pallas interpreter")
    return dev


def make_requests(vocab: int, seed: int) -> list[Request]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    lens[0] = PROMPT_LENS[1]
    system = rng.integers(0, vocab, SHARED_PREFIX)
    reqs = []
    for uid, n in enumerate(lens):
        prompt = rng.integers(0, vocab, int(n))
        if uid < N_REQUESTS // 2:
            prompt[:SHARED_PREFIX] = system
        reqs.append(Request(uid=uid, prompt=prompt.astype(np.int32),
                            max_new_tokens=NEW_TOKENS))
    return reqs


def serve(model, params, reqs: list[Request]) -> dict:
    """Serve ``reqs`` through make_engine and check the served output."""
    kv = KVSpec(kind="paged", block_size=BLOCK, prefix_cache=True)
    eng = make_engine(model, params, EngineConfig(
        max_batch=N_REQUESTS, max_len=MAX_LEN, mode="continuous", kv=kv))
    t0 = time.perf_counter()
    for r in reqs:
        if not eng.submit(r):
            raise RuntimeError(f"request {r.uid} refused at submit")
    steps = eng.drain()
    wall = time.perf_counter() - t0
    vocab = model.cfg.vocab_size
    _check(len(eng.finished) == len(reqs), f"{len(eng.finished)} of {len(reqs)} finished")
    for r in eng.finished:
        _check(len(r.out_tokens) == NEW_TOKENS, f"request {r.uid}: {NEW_TOKENS} tokens")
        _check(all(0 <= t < vocab for t in r.out_tokens), f"request {r.uid}: ids in vocab")
    logits = eng.last_logits
    _check(logits.shape == (N_REQUESTS, 1, vocab), f"decode logits shape {logits.shape}")
    _check(bool(jnp.isfinite(logits).all()), "decode logits finite")
    return {"engine": eng, "steps": steps, "wall": wall}


def teacher_forced_diff(model, params, reqs: list[Request]) -> dict:
    """Prefill ``reqs`` into a fresh paged store, then decode
    TEACHER_STEPS steps with both attention paths on the same inputs
    (the kernel's greedy token is fed to both)."""
    cfg = model.cfg
    store = make_kvstore(model, N_REQUESTS, MAX_LEN, KVSpec(kind="paged", block_size=BLOCK),
                         ragged=True)
    prompts = [r.prompt for r in reqs]
    logits, batch = PrefillRunner(model, params, MAX_LEN).run_batch(prompts)
    for i, p in enumerate(prompts):
        n = int(p.shape[0])
        store.admit(i, {"k": batch["k"][:, i:i + 1], "v": batch["v"][:, i:i + 1],
                        "pos": jnp.int32(n)}, n)
    kern = jax.jit(functools.partial(transformer.decode_step_paged_lm, cfg, impl="kernel"))
    ref = jax.jit(functools.partial(transformer.decode_step_paged_lm, cfg, impl="ref"))
    tok = sample_last(logits)[:, None]
    active = list(range(N_REQUESTS))
    worst, agree = 0.0, []
    for _ in range(TEACHER_STEPS):
        view = store.kernel_view(active)
        lk, rows_k, rows_v = kern(params, view, tok)
        lr, _, _ = ref(params, view, tok)
        lk32, lr32 = np.asarray(lk, np.float32), np.asarray(lr, np.float32)
        _check(np.isfinite(lk32).all() and np.isfinite(lr32).all(), "logits finite")
        rel = float(np.abs(lk32 - lr32).max() / np.abs(lr32).max())
        worst = max(worst, rel)
        agree.append(float((lk32.argmax(-1) == lr32.argmax(-1)).mean()))
        store.absorb_rows(rows_k, rows_v, active)
        tok = sample_last(lk)[:, None]
    return {"rel_diff": worst, "argmax_agree": agree,
            "kernel_hlo": kern.lower(params, store.kernel_view(active), tok).compile().as_text()}


def one_chip(seed: int) -> None:
    cfg = configs.get(SERVE_MODEL)
    model = build(cfg)
    params = init_serving_params(model, jax.random.PRNGKey(seed))
    param_bytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads / {cfg.n_kv_heads} KV heads, d_ff {cfg.d_ff}, "
          f"vocab {cfg.vocab_size}; resident parameter bytes {param_bytes}")

    reqs = make_requests(cfg.vocab_size, seed)
    out = serve(model, params, reqs)
    eng = out["engine"]
    served = sum(len(r.out_tokens) for r in eng.finished)
    kvs = eng.kv.stats
    print(f"served {len(eng.finished)} requests (prompts "
          f"{min(len(r.prompt) for r in reqs)}-{max(len(r.prompt) for r in reqs)} "
          f"tokens), {served} new tokens in {out['steps']} engine steps; "
          f"prefix-cache hit tokens {eng.stats['prefix_hit_tokens']}, "
          f"pool {kvs['n_blocks']} blocks of {BLOCK}, peak {kvs['peak_blocks']} in use")
    print(f"smoke timing, not a benchmark: served window {out['wall']:.3f} s "
          f"wall, compiles included")
    _check(eng.stats["prefix_hit_tokens"] > 0, "the shared system prompt hits the prefix cache")

    _require_kernel(eng._decode_paged.lower(
        params, eng.kv.kernel_view([]), eng.tokens).compile().as_text(), "decode step")
    _require_kernel(sample_last.lower(eng.last_logits).compile().as_text(), "sample_last")
    print("tpu_custom_call present in the compiled decode and sample_last programs")

    tf = teacher_forced_diff(model, params, reqs)
    _require_kernel(tf["kernel_hlo"], "impl='kernel' decode step")
    print(f"kernel vs reference decode, {TEACHER_STEPS} teacher-forced steps: largest "
          f"|logit diff| / largest |ref logit| = {tf['rel_diff']!r} (tolerance {LOGIT_TOL}); "
          f"greedy agreement per step {tf['argmax_agree']}")
    _check(tf["rel_diff"] <= LOGIT_TOL, "kernel and reference logits agree")


def four_chips(seed: int) -> None:
    from repro.apps.mapreduce import CorpusCfg, run_wordcount
    from repro.train.optimizer import OptConfig, init_opt_state
    from repro.train.train_step import TrainStepConfig, make_jitted_step
    from repro.utils.compat import make_mesh

    devices = set(jax.devices())
    mesh = make_mesh((4,), ("data",))
    _check(set(mesh.devices.flat) == devices, "the mesh spans the four chips")
    corpus = CorpusCfg(n_docs_per_row=64, words_per_doc=2048, vocab=32768, seed=seed)
    h_ref, _ = run_wordcount(mesh, "reference", corpus)
    h_dec, _ = run_wordcount(mesh, "decoupled", corpus, alpha=0.25)
    np.testing.assert_array_equal(h_ref, h_dec)
    print(f"mapreduce: reference == decoupled (alpha 0.25) histogram, bit-identical; "
          f"{int(h_ref.sum())} words over {corpus.vocab} keys")

    cfg = dataclasses.replace(configs.get(TRAIN_MODEL), n_layers=TRAIN_LAYERS,
                              dtype=jnp.float32)
    model = build(cfg)
    mesh = make_mesh((4, 1), ("data", "model"))
    params = model.init(jax.random.PRNGKey(seed))
    opt_cfg = OptConfig(kind="sgdm", lr=1.0, beta1=0.0, warmup_steps=0, grad_clip=0.0,
                        weight_decay=0.0, min_lr_ratio=1.0, total_steps=1)
    opt_state = init_opt_state(opt_cfg, params)
    batch = synthetic_batch(cfg, TRAIN_BATCH, TRAIN_SEQ, jax.random.PRNGKey(seed + 1))
    # the last chip is the reduce group: its rows carry no tokens in
    # either mode, so both steps see the same gradient
    mask = np.asarray(batch["mask"]).copy()
    mask[-TRAIN_BATCH // 4:] = 0.0
    batch["mask"] = jnp.asarray(mask)
    params_like = jax.eval_shape(lambda: params)
    new = {}
    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        for name, kw in (("conventional", {"mode": "conventional"}),
                         ("decoupled", {"mode": "decoupled", "reduce_alpha": 0.25,
                                        "wire_chunk_bytes": WIRE_CHUNK_BYTES})):
            step, in_sh = make_jitted_step(model, mesh, opt_cfg, TrainStepConfig(**kw),
                                           params_like, batch, donate=False)
            placed = jax.device_put(batch, in_sh[2])
            rows = {s.device for s in placed["tokens"].addressable_shards}
            _check(rows == devices, f"{name}: batch rows on all four chips")
            if name == "decoupled":  # the chunk_accumulate wave fold
                _require_kernel(step.lower(params, opt_state, placed).compile().as_text(),
                                "decoupled train step")
            out = step(params, opt_state, placed)[0]
            for leaf in jax.tree.leaves(out):
                _check(leaf.sharding.device_set == devices, f"{name}: outputs on the mesh")
            new[name] = out
    upd_c = [a - p for a, p in zip(jax.tree.leaves(new["conventional"]), jax.tree.leaves(params))]
    upd_d = [a - p for a, p in zip(jax.tree.leaves(new["decoupled"]), jax.tree.leaves(params))]
    scale = max(float(jnp.max(jnp.abs(u))) for u in upd_c)
    diff = max(float(jnp.max(jnp.abs(c - d))) for c, d in zip(upd_c, upd_d))
    rel = diff / scale
    print(f"train step at {cfg.name} widths ({cfg.n_layers} layers, batch {TRAIN_BATCH}x"
          f"{TRAIN_SEQ}): decoupled (alpha 0.25, {WIRE_CHUNK_BYTES}-byte chunks) vs "
          f"conventional, largest update diff / largest update = {rel!r} "
          f"(tolerance {UPDATE_TOL})")
    _check(scale > 0 and rel <= UPDATE_TOL, "decoupled and conventional updates agree")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    dev = _require_tpu(args.chips)
    cache = use_compile_cache()
    log = _CompileLog()
    print(f"device {dev.device_kind} x{len(jax.devices())}, jax {jax.__version__}, "
          f"compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(args.seed)
    else:
        four_chips(args.seed)
    peak = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    print(f"compiles {log.count} ({log.cache_hits} from the persistent cache), "
          f"{log.seconds:.1f} s compiling; run {time.perf_counter() - t0:.1f} s; "
          f"peak_bytes_in_use per chip {peak}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
