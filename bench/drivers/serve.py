"""Serving cells: a closed loop of clients in front of ``make_engine``.

Set-up makes the weights on the device from the seed, builds the engine
the cell file describes, sweeps every prefill shape the mix can ask for
(each prompt bucket at each packed batch up to ``warm_batch``, each
prompt length once cold and once behind a cached shared prefix), then
runs the cell's own clients for ``warm_ticks`` engine steps. The window
follows without a break: clients send, the engine steps, and the host
clock at the end of each step stamps the tokens that appeared in it.

Metrics over the window: gaps between consecutive tokens that arrived
in it (their 90th and 99th percentiles), output tokens per second, and
the mean time to first token of the requests sent in it (send to the end
of the step that produced ``out_tokens[0]``). ``BENCHMARK.json`` picks
which of them a cell reports. Correctness: a sample of the finished
requests, the longest among them, scored by the float32 reference of
the configuration's architecture (``bench/reference/<model_type>.py``).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import arch, program, traffic, weights
from bench.common import device_record

WARM_SEED = 0x5EED  # the shape sweep's prompts: the same in every run


def _buckets(lo: int, hi: int, max_len: int) -> list[int]:
    from repro.serve.engine import prefill_bucket

    return sorted({prefill_bucket(n, max_len=max_len) for n in range(lo, hi + 1)})


class ClosedLoop:
    """Clients, their requests in flight, and the token clock."""

    def __init__(self, eng, specs, clients: int, initial_per_tick: int, jax):
        from repro.serve import Request

        self.Request = Request
        self.eng, self.specs, self.jax = eng, specs, jax
        self.ready = clients  # clients waiting to send
        self.initial_left = clients
        self.initial_per_tick = initial_per_tick
        self.next = 0
        self.inflight: dict[int, dict] = {}
        self.done: list[dict] = []
        self.sending = True
        self.refused = 0
        self.ticks: list[dict] = []  # per step, while recording
        self.recording = False
        self.seen_prompts: set[bytes] = set()

    def _send(self, now: float) -> None:
        n = self.ready
        if self.initial_left:
            n = min(n, self.initial_per_tick)
        for _ in range(n):
            spec = self.specs[self.next % len(self.specs)]
            uid = self.next
            self.next += 1
            req = self.Request(uid=uid, prompt=spec.prompt, max_new_tokens=spec.max_new_tokens)
            self.ready -= 1
            self.initial_left = max(0, self.initial_left - 1)
            if not self.eng.submit(req):
                self.refused += 1
                self.ready += 1
                continue
            self.inflight[uid] = {"req": req, "sent": now, "seen": 0, "last": None,
                                  "first_at": None, "first_token": None, "gaps": []}

    def tick(self) -> tuple[float, int]:
        """One engine step; returns the host clock at its end and the
        output tokens that appeared in it."""
        TA = self.jax.profiler.TraceAnnotation
        if self.sending and self.ready:
            with TA("bench.submit"):
                self._send(time.perf_counter())
        skips = self.eng.stats["prefill_skips"]
        with TA("bench.engine_step"):
            self.eng.step()
        t = time.perf_counter()
        with TA("bench.bookkeeping"):
            new = self._account(t, self.eng.stats["prefill_skips"] - skips)
        return t, new

    def _account(self, t: float, full_hits: int) -> int:
        admitted, attended, finished, new = [], [], [], 0
        for uid, st in self.inflight.items():
            req = st["req"]
            n = len(req.out_tokens)
            if n == st["seen"]:
                continue
            new += n - st["seen"]
            if st["seen"] == 0:
                st["first_at"] = t
                st["first_token"] = self._first_token(req)
                admitted.append(req)
            else:
                st["gaps"].append((t, t - st["last"]))
            st["last"] = t
            st["seen"] = n
            attended.append(int(req.prompt.shape[0]) + n)
            if req.done:
                finished.append(uid)
        for uid in finished:
            st = self.inflight.pop(uid)
            st["done_at"] = t
            self.done.append(st)
            self.ready += 1
        if self.recording:
            cold = [int(r.prompt.shape[0]) for r in admitted]
            # whole-prompt prefix hits skip prefill: they are repeats
            repeats = [int(r.prompt.shape[0]) for r in admitted
                       if r.prompt.tobytes() in self.seen_prompts]
            for n in sorted(repeats)[:full_hits]:
                cold.remove(n)
            self.ticks.append({"t": t, "attended": attended, "prefill_lens": cold,
                               "prefill_tokens": sum(cold)})
        for r in admitted:
            self.seen_prompts.add(r.prompt.tobytes())
        return new

    def _first_token(self, req) -> int | None:
        """The token prefill produced for ``req``, fed to the first
        decode step but not put in ``out_tokens``: the request's own
        ``first_token`` where the program sets one; else the prefix
        cache's entry for the whole prompt, which keeps it; None if
        neither has it."""
        first = getattr(req, "first_token", None)
        if first is not None:
            return int(first)
        prefix = getattr(self.eng.kv, "prefix", None)
        if prefix is None:
            return None
        entry = prefix.entries.get(("full", np.asarray(req.prompt, np.int64).tobytes()))
        return None if entry is None else int(entry.first)


def _sweep(eng, mix: dict, vocab: int, max_len: int, warm_batch: int) -> None:
    """Every packed-prefill shape the mix can produce, through
    submit/step on the empty engine, one output token each: each prompt
    bucket at each batch up to ``warm_batch``, and each prompt length in
    each bucket it can be padded to (a packed batch pads to its longest
    prompt), once cold and once behind a cached shared prefix."""
    from repro.serve import Request

    rng = traffic.rng_for(WARM_SEED, 7)
    pm = mix["prompt"]
    lattice = list(range(int(pm["min"]), int(pm["max"]) + 1, int(pm.get("quantum", 1))))
    uid = [10**9]

    def run(prompts):
        for p in prompts:
            uid[0] += 1
            if not eng.submit(Request(uid=uid[0], prompt=p, max_new_tokens=1)):
                raise RuntimeError("the empty engine refused a warm-up request")
        eng.drain()

    def rand(n):
        return rng.integers(0, vocab, n, dtype=np.int64).astype(np.int32)

    sp = mix.get("shared_prefix") or {"count": 0, "tokens": 0}
    shared = rand(sp["tokens"]) if sp["count"] else None
    for b in _buckets(lattice[0], lattice[-1], max_len):
        top = max(n for n in lattice if n <= b)
        for k in range(1, warm_batch + 1):
            run([rand(top) for _ in range(k)])
        for prefixed in ((False, True) if shared is not None else (False,)):
            if prefixed:
                # (re-)registers the shared prefix, which the cold
                # prompts before it may have pushed out of the cache
                run([shared.copy()])
            group = []
            for n in (n for n in lattice if n <= b):
                p = rand(n)
                if prefixed and n > sp["tokens"]:
                    p[: sp["tokens"]] = shared
                group.append(p)
                if len(group) == warm_batch - 1:
                    run(group + [rand(top)])
                    group = []
            if group:
                run(group + [rand(top)])


def _warm_scatter(kv, slots: int) -> None:
    """The store's decode-row scatter for every count of fresh tail
    blocks one step can allocate (none up to one per slot), with every
    slot active: its program is keyed on that count. Arguments are built
    as ``PagedKVStore.absorb_rows`` builds them; each result is dropped,
    so the pool stays as it was."""
    import jax
    import jax.numpy as jnp

    n_layers, _, _, width = kv.k_pool.shape
    rows = jnp.zeros((n_layers, slots, width), kv._cache_dtype)
    pools = ((kv.k_pool, kv.v_pool, kv.k_scale, kv.v_scale) if kv.quantized
             else (kv.k_pool, kv.v_pool))
    idx = list(range(slots))
    blocks = np.zeros(slots, np.int32)
    offs = np.zeros(slots, np.int64)
    for n in range(slots + 1):
        out = kv._scatter(*pools, rows, rows, jnp.asarray(idx, jnp.int32),
                          jnp.asarray(blocks, jnp.int32), jnp.asarray(offs, jnp.int32),
                          jnp.asarray([0] * n, jnp.int32))
        jax.block_until_ready(out)
        del out


def _pct(values, q: float) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


def run(ctx) -> tuple[dict, dict, dict]:
    """One run of a serving cell: (result, checks, record)."""
    jax = ctx.jax
    from repro.models import build
    from repro.serve import EngineConfig, KVSpec, make_engine

    cell, cfg, mix = ctx.cell, ctx.config, ctx.mix
    eng_cfg = cell["engine"]
    model = build(program.arch_config(cfg, cfg["compute_dtype"]))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    params = weights.make_params(tree, ctx.seed, program.stored_dtype(cfg),
                                 arch=arch.load(cfg).module)
    kv = KVSpec(kind="paged", block_size=eng_cfg["block_size"], n_blocks=eng_cfg["n_blocks"],
                prefix_cache=eng_cfg["prefix_cache"],
                prefix_capacity=eng_cfg.get("prefix_capacity", 256))
    eng = make_engine(model, params, EngineConfig(
        max_batch=eng_cfg["max_batch"], max_len=eng_cfg["max_len"], mode="continuous",
        eos_id=-1, kv=kv))
    vocab = cfg["vocab_size"]
    _sweep(eng, mix, vocab, eng_cfg["max_len"], cell["warm_batch"])
    _warm_scatter(eng.kv, eng_cfg["max_batch"])
    specs = traffic.closed_loop_requests(mix, vocab, ctx.seed)
    loop = ClosedLoop(eng, specs, mix["clients"], mix["initial_per_tick"], jax)
    for _ in range(cell["warm_ticks"]):
        loop.tick()
    jax.block_until_ready(eng.tokens)
    setup_s = time.perf_counter() - ctx.t_start

    # -- the window ------------------------------------------------------
    log = ctx.compile_log
    log.window = 0
    log.in_window = True
    tracer = ctx.tracer
    t0 = time.perf_counter()
    first_uid, refused0 = loop.next, loop.refused
    tokens, t_end, n_ticks = 0, t0, 0
    if tracer:
        tracer.start()
        loop.recording = True
    while t_end - t0 < ctx.seconds:
        t_end, new = loop.tick()
        tokens += new
        n_ticks += 1
        if tracer and tracer.running and t_end - t0 >= ctx.trace_seconds:
            jax.block_until_ready(eng.tokens)
            tracer.stop()
            loop.recording = False
    window_s = t_end - t0
    log.in_window = False
    if tracer and tracer.running:
        jax.block_until_ready(eng.tokens)
        tracer.stop()
        loop.recording = False
    # first tokens of requests sent in the window that are still due are
    # waited for; their wait counts
    loop.sending = False
    deadline = time.perf_counter() + 60.0
    while (any(st["first_at"] is None and st["sent"] >= t0 for st in loop.inflight.values())
           and time.perf_counter() < deadline):
        loop.tick()
    window_req = [st for st in loop.done + list(loop.inflight.values())
                  if st["req"].uid >= first_uid]
    ttft = [st["first_at"] - st["sent"] for st in window_req if st["first_at"] is not None]
    gaps = [g for st in loop.done + list(loop.inflight.values())
            for (ta, g) in st["gaps"] if t0 < ta <= t_end]
    never = sum(st["first_at"] is None for st in window_req)
    refused = loop.refused - refused0
    dev_rec = device_record(jax.devices()[: ctx.chips])

    # -- correctness, once the program's state is freed ----------------------
    finished = [st for st in loop.done
                if st["first_token"] is not None and st["done_at"] <= t_end]
    unknown_first = sum(st["first_token"] is None for st in loop.done)
    recorded = {"ticks": loop.ticks}
    del eng, params, loop
    gc.collect()
    checks, readings = check(ctx, cfg, cell, finished)
    metrics = {
        "ttft_mean_ms": {"value": 1e3 * float(np.mean(ttft)), "unit": "ms"},
        "itl_p90_ms": {"value": 1e3 * _pct(gaps, 90), "unit": "ms"},
        "itl_p99_ms": {"value": 1e3 * _pct(gaps, 99), "unit": "ms"},
        "out_tok_s": {"value": tokens / window_s, "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    ok = all(c["value"] <= c["limit"] for c in checks.values()) and never == 0
    result = {"correct": ok, "attempted": len(window_req) + refused,
              "failed": never + refused, "metrics": metrics, "device": dev_rec}
    record = {"kind": "serve", "setup_s": setup_s, "window_s": window_s, "ticks": n_ticks,
              "tokens": tokens, "requests_sent": len(window_req), "ttft_n": len(ttft),
              "gaps_n": len(gaps), "unknown_first_token": unknown_first,
              "compiles_in_window": log.window, "window_compiled": log.window_names,
              "compiles_total": log.count, "compile_s": log.seconds,
              "cache_hits": log.cache_hits, "readings": readings, "traced": recorded}
    return result, checks, record


def check(ctx, cfg: dict, cell: dict, finished: list) -> tuple[dict, dict]:
    """Score a seeded sample of finished requests, the longest among
    them, with the float32 reference of the configuration's
    architecture: for every served token (and the token prefill fed to
    the first decode step), the gap by which its reference logit lies
    below the reference's best."""
    jax = ctx.jax
    jnp = jax.numpy
    chk = cell["check"]
    if not finished:
        return {"logit_gap": {"value": float("inf"), "limit": chk["logit_gap"]}}, {}
    total = lambda st: int(st["req"].prompt.shape[0]) + len(st["req"].out_tokens)  # noqa: E731
    longest = max(range(len(finished)), key=lambda i: total(finished[i]))
    rest = [i for i in range(len(finished)) if i != longest]
    rng = traffic.rng_for(ctx.seed, 11)
    pick = [longest] + list(rng.permutation(rest)[: chk["requests"] - 1])
    seq_len, n_pos = chk["pad_tokens"], chk["pad_positions"]
    reference = arch.load(cfg).reference
    W = reference.make_weights(cfg, ctx.seed, program.stored_dtype(cfg))
    gaps, cgaps, scored = [], [], 0
    for i in pick:
        st = finished[i]
        prompt = np.asarray(st["req"].prompt, np.int64)
        served = [st["first_token"]] + list(st["req"].out_tokens)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int64)])
        n = len(prompt)
        positions = np.arange(n - 1, n - 1 + len(served))
        toks = np.zeros(seq_len, np.int32)
        toks[: len(seq)] = seq
        pos = np.full(n_pos, n - 1, np.int32)
        pos[: len(positions)] = positions
        ref = reference.scored_logits(cfg, W, toks, pos)[: len(served)]
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(ref, jnp.asarray(served)[:, None], axis=-1)[:, 0]
        gaps.append(float(jnp.max(best - got)))
        if ctx.control:
            ctl = reference.scored_logits(cfg, W, toks, pos, precision="fp8")[: len(served)]
            pick_c = jnp.argmax(ctl, axis=-1)
            got_c = jnp.take_along_axis(ref, pick_c[:, None], axis=-1)[:, 0]
            cgaps.append(float(jnp.max(best - got_c)))
        scored += len(served)
    readings = {"logit_gap": max(gaps), "requests_scored": len(pick), "tokens_scored": scored,
                "per_request_gap": gaps}
    if ctx.control:
        readings["control_logit_gap"] = max(cgaps)
        readings["control_per_request_gap"] = cgaps
    value = readings["control_logit_gap"] if ctx.control else readings["logit_gap"]
    return {"logit_gap": {"value": value, "limit": chk["logit_gap"]}}, readings
