"""Serving driver: batched requests through the slot-based engine with
decoupled analytics samples per tick (the paper's Listing-1 pattern
applied to an inference fleet).

`--disagg` routes the trace through the disaggregated engine instead: a
prefill group feeds KV caches to the decode slot pool through the
handoff channel (see repro/serve/disagg.py).

`--scenario NAME` replays a named, reproducible traffic scenario
(repro/serve/traffic.py) through the ServeFleet scheduler: multi-tenant
WFQ with SLO classes and token-budget admission, per-tenant latency
accounting in the FleetLedger. `--adapt` additionally closes the
measure -> plan -> regroup loop (repro/serve/fleet.py): the
prefill/decode split re-sizes against the live traffic mix.

`--continuous` switches any engine to slot-level continuous batching
(a slot freed by retirement refills the same tick); `--paged` adds the
paged KV store with the cross-tenant prefix cache. Every combination
builds through the one `make_engine(model, params, cfg)` entry point —
the driver below never branches on engine type.

`--spec` serves through the speculative draft->verify engine
(repro/serve/spec.py): a small zoo draft model (`--draft NAME`) streams
`--spec-k`-token blocks to the target model, which scores all k
positions in one batched verify forward and rolls the KV caches back to
the accept point. Greedy speculative streams are bitwise-identical to
target-only greedy; the speedup is the accepted-tokens-per-verify-step
multiple printed at the end.

`--fail-at TICK` / `--preempt-at TICK` inject a fault mid-replay
(repro/serve/faults.py): a device loss orphans the dying rows'
in-flight requests (re-admitted at their original arrival ticks —
zero lost), a preemption stages them to host and the fleet re-grows
when the rows return. Fault flags need `--scenario` and route through
the FleetEngine in continuous mode.

`--model NAME` picks the zoo model (reduced smoke widths by default;
`--full` builds the published widths). Weights are random, made by
`init_serving_params`: matrices and embeddings in the model dtype.

Run:  PYTHONPATH=src python examples/serve_lm.py [--disagg]
      PYTHONPATH=src python examples/serve_lm.py --scenario bursty-prefix --paged
      PYTHONPATH=src python examples/serve_lm.py --scenario bursty-multitenant --adapt
      PYTHONPATH=src python examples/serve_lm.py --scenario bursty-multitenant --fail-at 12
      PYTHONPATH=src python examples/serve_lm.py --spec --spec-k 4 --paged
"""
import argparse
import time

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get, get_smoke
from repro.models import build, init_serving_params
from repro.serve import (
    DisaggConfig,
    EngineConfig,
    KVSpec,
    Request,
    make_engine,
)
from repro.serve.sched import FleetScheduler
from repro.serve.traffic import SCENARIOS, replay, scenario
from repro.utils.compile_cache import use_compile_cache


def drive_legacy(eng, cfg, n_requests=10):
    rng = np.random.default_rng(0)
    for i in range(n_requests):
        prompt = rng.integers(0, cfg.vocab_size, size=rng.integers(2, 6))
        eng.submit(Request(uid=i, prompt=prompt.astype(np.int32),
                           max_new_tokens=int(rng.integers(4, 12))))
    analytics = []
    while not eng.idle():
        eng.step()
        analytics.append(eng.workload_sample())  # -> decoupled analytics group
        if len(analytics) > 500:
            raise RuntimeError("engine did not drain")
    return n_requests, analytics


def drive_scenario(eng, cfg, sc, **fault_kw):
    analytics = []
    pairs = replay(eng, sc, cfg.vocab_size,
                   on_tick=lambda e: analytics.append(e.workload_sample()),
                   **fault_kw)
    return len(pairs), analytics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="qwen2.5-3b", choices=ARCH_NAMES,
                    help="zoo model to serve")
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the reduced smoke config")
    ap.add_argument("--disagg", action="store_true",
                    help="serve through the prefill/decode-disaggregated engine")
    ap.add_argument("--scenario", default=None, choices=sorted(SCENARIOS),
                    help="replay a named traffic scenario through the "
                         "multi-tenant ServeFleet scheduler")
    ap.add_argument("--adapt", action="store_true",
                    help="close the prefill/decode re-sizing loop "
                         "(implies --disagg, needs --scenario)")
    ap.add_argument("--continuous", action="store_true",
                    help="slot-level continuous batching (same-tick refill)")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV blocks + cross-tenant prefix cache "
                         "(implies --continuous)")
    ap.add_argument("--spec", action="store_true",
                    help="speculative draft->verify decoding "
                         "(implies --continuous)")
    ap.add_argument("--spec-k", type=int, default=4, metavar="K",
                    help="draft block length per verify step (with --spec)")
    ap.add_argument("--draft", default="qwen1.5-0.5b",
                    help="zoo name of the draft model (with --spec)")
    ap.add_argument("--fail-at", type=int, default=None, metavar="TICK",
                    help="lose --fault-rows rows WITHOUT notice at TICK "
                         "(device loss; orphans re-admitted, zero lost)")
    ap.add_argument("--preempt-at", type=int, default=None, metavar="TICK",
                    help="preempt --fault-rows rows WITH notice at TICK "
                         "(slots stage to host; rows return after "
                         "--preempt-duration ticks)")
    ap.add_argument("--fault-rows", type=int, default=1)
    ap.add_argument("--preempt-duration", type=int, default=8)
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="record a Chrome/Perfetto trace of the run to PATH "
                         "(open at ui.perfetto.dev or chrome://tracing)")
    args = ap.parse_args()
    faulted = args.fail_at is not None or args.preempt_at is not None

    if args.trace:
        from repro.obs import trace as obs_trace

        obs_trace.enable()  # before engine build: compile spans land too

    use_compile_cache()
    cfg = get(args.model) if args.full else get_smoke(args.model)
    model = build(cfg)
    params = init_serving_params(model, jax.random.PRNGKey(0))

    sc = scenario(args.scenario) if args.scenario else None
    sched = FleetScheduler(sc.tenants, token_budget=2000, aging=0.05) if sc else None

    # the serving mode rides on the shared ServeConfig base: the same
    # two fields pick batching + KV for every engine construction
    batching = ("continuous" if (args.continuous or args.paged or faulted)
                else "aligned")
    kv = (KVSpec(kind="paged", block_size=16, prefix_cache=True)
          if args.paged else KVSpec())

    if args.spec:
        if args.disagg or args.adapt or faulted:
            raise SystemExit("--spec composes with --paged/--scenario, not "
                             "--disagg/--adapt/fault flags")
        from repro.serve import SpecConfig

        engine_cfg = SpecConfig(max_batch=4, max_len=160, mode="continuous",
                                kv=kv, spec_k=args.spec_k, draft=args.draft)
        mode = f"speculative k={args.spec_k}"
    elif args.adapt or faulted:
        if sc is None:
            raise SystemExit("--adapt / fault injection need --scenario")
        from repro.serve import FleetConfig

        adapt = None
        if args.adapt:
            from repro.core.adapt import AdaptPolicy

            adapt = AdaptPolicy(window=4, cooldown=4,
                                speedup_threshold=1.1, row_budget=5)
        engine_cfg = FleetConfig(
            n_rows=8, prefill_rows=2, slots_per_row=1, max_len=160,
            prefill_chunk=16, mode=batching, kv=kv, adapt=adapt)
        mode = "adaptive-disagg" if args.adapt else "fleet"
        if faulted:
            mode += "+faults"
    elif args.disagg:
        engine_cfg = DisaggConfig(n_prefill_rows=2, decode_slots=4, max_len=160,
                                  mode=batching, kv=kv)
        mode = "disaggregated"
    else:
        engine_cfg = EngineConfig(max_batch=4, max_len=160,
                                  mode=batching, kv=kv)
        mode = "colocated"
    if batching == "continuous":
        mode += "+paged" if args.paged else "+continuous"
    eng = make_engine(model, params, engine_cfg, sched=sched)

    t0 = time.time()
    if sc is not None:
        n_requests, analytics = drive_scenario(
            eng, cfg, sc,
            fail_at=args.fail_at, preempt_at=args.preempt_at,
            fault_rows=args.fault_rows,
            preempt_duration=args.preempt_duration)
    else:
        n_requests, analytics = drive_legacy(eng, cfg)
    dt = time.time() - t0

    tokens_out = eng.stats["tokens_out"]
    dev = jax.devices()[0]
    print(f"[{mode}] {cfg.name}: served {n_requests} requests, {tokens_out} tokens "
          f"in {len(analytics)} ticks ({tokens_out / dt:.1f} tok/s wall, compiles "
          f"included, on {dev.platform} {dev.device_kind} x{len(jax.devices())})")
    occ = np.mean([a["active_slots"] for a in analytics])
    print(f"mean slot occupancy {occ:.2f}, final queue depth "
          f"{analytics[-1]['queue_depth']}")
    if args.disagg and not args.adapt:
        ttft = [r.first_token_tick - r.submitted_tick for r in eng.finished]
        print(f"prefills handed off: {eng.stats['handoffs']}, "
              f"mean TTFT {np.mean(ttft):.1f} ticks")
    if args.paged:
        print(f"prefix cache: {eng.stats['prefix_hit_tokens']} hit tokens, "
              f"{eng.stats['prefill_skips']} prefill skips")
    if args.spec:
        acc = eng.ledger.acceptance_rate()
        verify_calls = max(1, eng.stats["verify_calls"])
        print(f"speculative: acceptance rate {acc:.2f}, "
              f"rows draft/verify = {eng.draft_rows}/"
              f"{eng.n_rows - eng.draft_rows}, "
              f"{eng.stats['tokens_out'] / verify_calls:.2f} "
              f"tokens per verify step "
              f"(drafted {eng.stats['drafted']}, "
              f"accepted {eng.stats['accepted']})")
    if args.adapt:
        print(f"regroups: {eng.regroups} (deferred {eng.deferrals}), final "
              f"prefill rows {eng.prefill_rows}/{eng.cfg.n_rows}, "
              f"decode slots {eng.decode_slots}")
    if faulted:
        finished = {r.uid for r in eng.finished}
        rec = eng.recoveries
        print(f"faults: {len(eng.fault_log)} events, recoveries "
              f"staged={rec['staged']} restored={rec['restored']} "
              f"retried={rec['retried']}, regrows={eng.regrows}, "
              f"rows {eng.n_rows}/{eng.cfg.n_rows}, "
              f"lost {n_requests - len(finished)}")
    if sc is not None:
        snap = eng.ledger.snapshot()
        print(f"fleet: ttft p50/p99 = {snap['ttft_p50']:.0f}/{snap['ttft_p99']:.0f} "
              f"ticks, latency p99 = {snap['latency_p99']:.0f} ticks, "
              f"good tokens {snap['good_tokens']}/{snap['tokens_out']}")
        for name, rec in sorted(snap["by_tenant"].items()):
            print(f"  tenant {name:<12} n={rec['completions']:<4} "
                  f"ttft_p99={rec['ttft_p99']:.0f} "
                  f"latency_p99={rec['latency_p99']:.0f} "
                  f"good={rec['good_tokens']}")
    if args.trace:
        from repro.obs import export as obs_export
        from repro.obs import registry as obs_registry
        from repro.obs import trace as obs_trace

        tracer = obs_trace.get()
        obj = obs_export.write_trace(
            args.trace, metrics=obs_registry.get_registry().snapshot())
        life = tracer.lifecycle_report()
        print(f"trace: {len(obj['traceEvents'])} events "
              f"({tracer.dropped} dropped), "
              f"{life['begins']} request flows -> {args.trace}")


if __name__ == "__main__":
    main()
