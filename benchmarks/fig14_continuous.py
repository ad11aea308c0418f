"""Fig. 14 (beyond-paper): ContinuousServe — slot-level continuous
batching + paged KV + prefix cache vs the PR-5 aligned engine.

Three serving modes of the SAME colocated engine sweep the same
traffic:

  * ``aligned``     — the PR-5 phase loop: dense per-slot KV
    reservations, admission only at tick boundaries, batch-1 prefill
    serialized in front of decode. The baseline every claim is priced
    against.
  * ``continuous``  — slot-level continuous batching on the dense
    store: a slot freed by retirement refills the same tick, admitted
    prompts prefill packed in one jitted call.
  * ``paged``       — continuous batching on the paged KV store with
    the cross-tenant prefix cache, running 2x the slots at the SAME KV
    byte budget (``n_blocks`` = the dense engine's reservation): paged
    admission is gated on free *blocks*, not dense slot capacity, so
    the engine oversubscribes slots safely.

Methodology (DESIGN.md §8, the fig13 pattern): every mode replays the
scenario tick by tick on the real jitted engines; per-shape costs
(prefill per (bucket, batch), decode per batch, one cache migration)
are measured lazily with `bench` and each mode's tick trace is priced
on a virtual clock. Prefix-cache hits discount the prefill price to
the uncovered suffix — the compute a cache-aware prefill skips — and
whole-prompt hits skip prefill entirely (the engine really does).

Claimed (asserted):
  * under `bursty-multitenant` the paged mode beats the aligned engine
    on goodput at matched p99 latency;
  * paged KV memory tracks live tokens: private blocks in use equal
    the live-token block demand at EVERY tick, and the peak stays
    under the dense reservation for the same slot count;
  * under `bursty-prefix` the prefix cache lands hits (shared system
    prompts) and the paged win widens;
  * mode="aligned" reproduces the PR-5 engine loop BIT-FOR-BIT
    (decode logits per tick, emitted tokens, final KV) against an
    inline replica of the PR-5 `Engine.step`.

Run:  PYTHONPATH=src python benchmarks/fig14_continuous.py [--quick]
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__":
    os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    _REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for _p in (_REPO, os.path.join(_REPO, "src")):
        if _p not in sys.path:
            sys.path.insert(0, _p)

import dataclasses

import numpy as np

from benchmarks.util import bench, csv_row

LAST: dict = {}

MAX_LEN = 160
BLOCK_SIZE = 16
N_ROWS = 8  # the serving group is data-parallel over 8 rows (fig13)
SLOTS = 8  # the aligned / continuous-dense engines
PAGED_SLOTS = 16  # 2x oversubscription at the same KV byte budget
TOKEN_BUDGET = 2000
MATCHED_P99 = 1.0  # paged p99 must not exceed aligned p99


def _scenario(name: str, quick: bool):
    from repro.serve.traffic import scenario

    sc = scenario(name)
    tenants = tuple(
        dataclasses.replace(
            t, surge_at=(16 if quick else t.surge_at) if t.surge_at >= 0 else -1
        )
        for t in sc.tenants
    )
    return dataclasses.replace(
        sc, tenants=tenants, horizon=36 if quick else sc.horizon,
        max_prompt=min(sc.max_prompt, MAX_LEN - 32),
    )


# -- lazily measured per-shape costs --------------------------------------------


class _Costs:
    """Measured wall seconds per jitted call shape, memoized: prefill
    per (bucket, batch), decode per batch, one slot migration. Lazy so
    only the shapes a mode actually runs get benched."""

    def __init__(self, model, params):
        import jax
        import jax.numpy as jnp

        from repro.core.operators import migrate_cache_into_slot

        self._jnp = jnp
        self._model = model
        self._params = params
        self._pf = jax.jit(lambda p, t, n: model.prefill(p, t, length=n)[:2])
        self._dec = jax.jit(model.decode_step)
        self._pre: dict[tuple[int, int], float] = {}
        self._dcost: dict[int, float] = {}
        mig = jax.jit(migrate_cache_into_slot)
        cache_full = model.init_cache(SLOTS, MAX_LEN)
        cache_one = model.init_cache(1, 32)
        self.mig = bench(lambda: mig(cache_full, cache_one, 0), reps=3)

    def prefill(self, bucket: int, batch: int) -> float:
        key = (int(bucket), int(batch))
        if key not in self._pre:
            toks = self._jnp.zeros((batch, bucket), self._jnp.int32)
            lens = self._jnp.full((batch,), bucket, self._jnp.int32)
            n = lens if batch > 1 else bucket
            self._pre[key] = bench(
                lambda: self._pf(self._params, toks, n), reps=3
            )
        return self._pre[key]

    def decode(self, batch: int) -> float:
        b = int(batch)
        if b <= 0:
            return 0.0
        if b not in self._dcost:
            cache = self._model.init_cache(b, MAX_LEN)
            tok = self._jnp.zeros((b, 1), self._jnp.int32)
            self._dcost[b] = bench(lambda: self._dec(self._params, cache, tok),
                                   reps=3)
        return self._dcost[b]


# -- mode drivers ---------------------------------------------------------------


def _make_engine(model, params, mode: str, sc):
    from repro.serve import Engine, EngineConfig, KVSpec
    from repro.serve.sched import FleetScheduler

    if mode == "aligned":
        cfg = EngineConfig(max_batch=SLOTS, max_len=MAX_LEN)
    elif mode == "continuous":
        cfg = EngineConfig(max_batch=SLOTS, max_len=MAX_LEN, mode="continuous")
    else:  # paged: 2x slots, the dense engine's exact block budget
        cfg = EngineConfig(
            max_batch=PAGED_SLOTS, max_len=MAX_LEN, mode="continuous",
            kv=KVSpec(kind="paged", block_size=BLOCK_SIZE,
                      n_blocks=SLOTS * (MAX_LEN // BLOCK_SIZE) + 1,
                      prefix_cache=True),
        )
    return Engine(model, params, cfg,
                  sched=FleetScheduler(sc.tenants, token_budget=TOKEN_BUDGET))


def _drive(model, params, sc, costs: _Costs, mode: str) -> dict:
    from benchmarks.fig13_fleet import _stats
    from repro.serve.engine import prefill_bucket
    from repro.serve.traffic import replay

    eng = _make_engine(model, params, mode, sc)
    walls: list[float] = []
    kv_trace = {"peak_private": 0, "ticks": 0}

    def price_tick(e):
        tick = e.last_tick
        if mode == "aligned":
            # PR-5 pricing (fig13 colocated): the aligned loop issues
            # one batch-1 prefill call per admitted prompt, each
            # serialized in front of the row-parallel decode step
            pre = sum(
                costs.prefill(prefill_bucket(n, max_len=MAX_LEN), 1) + costs.mig
                for n in tick["prefill_lens"]
            )
        else:
            # continuous admission prefills packed — ONE jitted call,
            # data-parallel over the rows, priced at its (bucket,
            # per-row batch) shape — plus one slot install per cold
            # admission
            pre = sum(costs.prefill(b, -(-nb // N_ROWS))
                      for b, nb in tick["prefill_calls"])
            pre += costs.mig * len(tick["prefill_lens"])
        dec = costs.decode(-(-tick["decode_batch"] // N_ROWS)) \
            if tick["decode_batch"] else 0.0
        walls.append(pre + dec)
        if e.kv.kind == "paged":
            st = e.kv.stats
            # private (non-evictable) blocks never exceed the live-token
            # block demand; cross-slot prefix sharing is what makes the
            # inequality strict (tests/test_kvstore.py asserts equality
            # with the cache off)
            private = st["blocks_in_use"] - st["evictable_blocks"]
            assert private <= st["live_block_demand"], st
            kv_trace["peak_private"] = max(kv_trace["peak_private"], private)
            kv_trace["ticks"] += 1

    replay(eng, sc, model.cfg.vocab_size, on_tick=price_tick)
    out = {"mode": mode, **_stats(eng.ledger, walls)}
    out["prefills"] = eng.stats["prefills"]
    out["prefill_skips"] = eng.stats["prefill_skips"]
    out["prefix_hit_tokens"] = eng.stats["prefix_hit_tokens"]
    if eng.kv.kind == "paged":
        st = eng.kv.stats
        out["kv"] = {
            "n_blocks": st["n_blocks"],
            "peak_blocks": st["peak_blocks"],
            "peak_private_blocks": kv_trace["peak_private"],
            "dense_equiv_blocks": eng.cfg.max_batch * (MAX_LEN // BLOCK_SIZE),
            "prefix_hits": st.get("prefix_hits", 0),
        }
        # paged memory claim: live blocks tracked demand at every tick
        # (asserted above), and the pool the paged engine ever touched
        # stays below the dense reservation for the same slot count
        assert kv_trace["ticks"] > 0
        assert st["peak_blocks"] < out["kv"]["dense_equiv_blocks"], out["kv"]
    return out


# -- PR-5 bit-identity ----------------------------------------------------------


class _LegacyEngine:
    """The PR-5 `Engine` loop, verbatim (inline replica): dense cache
    attribute, batch-1 prefill + `migrate_cache_into_slot` admission,
    aligned decode over the whole pool. The reference mode="aligned"
    must be indistinguishable from."""

    def __init__(self, model, params, max_batch: int, max_len: int):
        import jax
        import jax.numpy as jnp

        from repro.core.operators import migrate_cache_into_slot
        from repro.serve.engine import PrefillRunner
        from repro.serve.sched import FleetScheduler

        self.params = params
        self.max_len = max_len
        self.sched = FleetScheduler.fifo()
        self.slots = [None] * max_batch
        self.finished = []
        self._decode = jax.jit(model.decode_step)
        self._prefill = PrefillRunner(model, params, max_len=max_len)
        self._migrate = jax.jit(migrate_cache_into_slot)
        self.cache = model.init_cache(max_batch, max_len)
        self.tokens = jnp.zeros((max_batch, 1), jnp.int32)
        self.last_logits = None
        self.tick = 0

    def submit(self, req):
        return self.sched.submit(req, now=self.tick)

    def idle(self):
        return self.sched.pending() == 0 and all(s is None for s in self.slots)

    def step(self):
        import jax.numpy as jnp

        free = [i for i, s in enumerate(self.slots) if s is None]
        for req in self.sched.take(self.tick, max_n=len(free)):
            slot = free.pop(0)
            self.slots[slot] = req
            logits, cache1 = self._prefill(req.prompt)
            self.cache = self._migrate(self.cache, cache1, slot)
            first = jnp.argmax(logits[0, -1]).astype(jnp.int32)
            self.tokens = self.tokens.at[slot, 0].set(first)
        self.tick += 1
        if all(s is None for s in self.slots):
            return
        logits, self.cache = self._decode(self.params, self.cache, self.tokens)
        self.last_logits = logits
        next_tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        next_np = np.asarray(next_tok)
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            req.out_tokens.append(int(next_np[i]))
            if len(req.out_tokens) >= req.max_new_tokens:
                req.done = True
                self.finished.append(req)
                self.slots[i] = None
        self.tokens = next_tok[:, None]


def check_aligned_bit_identity(model, params) -> dict:
    """single-fifo scenario: mode="aligned" == the PR-5 loop, decode
    logits bit-for-bit every tick, same tokens, same final KV."""
    from repro.serve import Engine, EngineConfig
    from repro.serve.traffic import scenario

    sc = scenario("single-fifo")
    by_tick: dict[int, list] = {}
    for e, r in sc.requests(model.cfg.vocab_size):
        by_tick.setdefault(e.tick, []).append(r)

    a = Engine(model, params, EngineConfig(max_batch=4, max_len=MAX_LEN))
    b = _LegacyEngine(model, params, max_batch=4, max_len=MAX_LEN)
    t = ticks = 0
    while t <= sc.horizon or not a.idle():
        for r in by_tick.get(t, []):
            a.submit(dataclasses.replace(r, out_tokens=[]))
            b.submit(dataclasses.replace(r, out_tokens=[]))
        a.step()
        b.step()
        if a.last_tick["decode_batch"]:
            np.testing.assert_array_equal(
                np.asarray(a.last_logits), np.asarray(b.last_logits)
            )
            ticks += 1
        t += 1
        assert t < 2000, "fifo scenario did not drain"
    assert b.idle()
    assert [r.out_tokens for r in a.finished] == [r.out_tokens for r in b.finished]
    for key in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(a.cache[key]), np.asarray(b.cache[key])
        )
    return {"ticks": ticks, "bit_identical": True}


# -- report ---------------------------------------------------------------------


def _report(quick: bool) -> list[str]:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_smoke
    from repro.models import build

    cfg = dataclasses.replace(get_smoke("tinyllama-1.1b"), dtype=jnp.float32)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    costs = _Costs(model, params)

    out = []
    records: dict[str, dict[str, dict]] = {}
    for sc_name in ("bursty-multitenant", "bursty-prefix"):
        sc = _scenario(sc_name, quick)
        records[sc_name] = {}
        for mode in ("aligned", "continuous", "paged"):
            rec = _drive(model, params, sc, costs, mode)
            records[sc_name][mode] = rec
            row = dict(
                tok_s=f"{rec['tput_tok_s']:.1f}",
                goodput=f"{rec['goodput_tok_s']:.1f}",
                latency_p99_us=f"{rec['latency_p99_s'] * 1e6:.0f}",
                ttft_p99_us=f"{rec['ttft_p99_s'] * 1e6:.0f}",
                prefill_skips=str(rec["prefill_skips"]),
            )
            if "kv" in rec:
                row["peak_blocks"] = str(rec["kv"]["peak_blocks"])
            out.append(csv_row(f"fig14_{sc_name}_{mode}", rec["total_s"] * 1e6,
                               **row))

    # headline claims: paged beats aligned on goodput at matched p99
    claims = {}
    for sc_name, recs in records.items():
        al, pg = recs["aligned"], recs["paged"]
        claims[sc_name] = {
            "goodput_win": pg["goodput_tok_s"] / max(al["goodput_tok_s"], 1e-12),
            "p99_ratio": pg["latency_p99_s"] / max(al["latency_p99_s"], 1e-12),
            "ttft_p99_ratio": pg["ttft_p99_s"] / max(al["ttft_p99_s"], 1e-12),
            "prefix_hit_tokens": pg["prefix_hit_tokens"],
            "peak_blocks": pg["kv"]["peak_blocks"],
            "dense_equiv_blocks": pg["kv"]["dense_equiv_blocks"],
        }
        assert claims[sc_name]["goodput_win"] > 1.0, claims[sc_name]
        assert claims[sc_name]["p99_ratio"] <= MATCHED_P99, claims[sc_name]
    # the prefix scenario actually exercises the cache
    assert claims["bursty-prefix"]["prefix_hit_tokens"] > 0, claims

    identity = check_aligned_bit_identity(model, params)

    LAST.clear()
    LAST.update(
        {
            "figure": "fig14_continuous",
            "quick": quick,
            "slots": {"dense": SLOTS, "paged": PAGED_SLOTS},
            "block_size": BLOCK_SIZE,
            "token_budget": TOKEN_BUDGET,
            "scenarios": records,
            "claims": claims,
            "aligned_bit_identity": identity,
        }
    )
    for sc_name, c in claims.items():
        out.append(
            csv_row(
                f"fig14_claims_{sc_name}",
                0.0,
                goodput_win=f"{c['goodput_win']:.2f}",
                p99_ratio=f"{c['p99_ratio']:.3f}",
                prefix_hit_tokens=str(c["prefix_hit_tokens"]),
                peak_blocks=f"{c['peak_blocks']}/{c['dense_equiv_blocks']}",
            )
        )
    out.append(
        csv_row(
            "fig14_aligned_bit_identity",
            0.0,
            ticks=str(identity["ticks"]),
            bit_identical=str(identity["bit_identical"]),
        )
    )
    return out


def run(mesh) -> list[str]:
    return _report(quick=False)


def run_quick(mesh) -> list[str]:
    """CI smoke: shorter horizon, earlier surge."""
    return _report(quick=True)


if __name__ == "__main__":
    import argparse
    import json

    parser = argparse.ArgumentParser()
    parser.add_argument("--quick", action="store_true")
    parser.add_argument(
        "--json",
        default=os.path.join(_REPO, "BENCH_serve_continuous.json"),
        help="where to write the ContinuousServe record",
    )
    args = parser.parse_args()

    print("name,us_per_call,derived")
    for line in (run_quick if args.quick else run)(None):
        print(line)
    with open(args.json, "w") as f:
        json.dump(LAST, f, indent=2, sort_keys=True, default=str)
        f.write("\n")
    print(f"# wrote {args.json}", file=sys.stderr)
