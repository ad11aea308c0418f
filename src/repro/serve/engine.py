"""Batched serving engine: slot-based batching over the model zoo's
prefill/decode interface, in two disciplines.

``mode="aligned"`` (default) is the paper's *conventional*
construction and the PR-5 behavior kept bit-identical: admission only
at the tick head, one shared decode cursor, dense KV. A long prefill
stalls every decode slot for the whole tick.

``mode="continuous"`` is slot-level continuous batching: a finished
prefill is inserted into a decode slot the same tick the slot frees
(admission runs again after retirement), admitted prompts prefill as
one packed multi-prompt call (`PrefillRunner.run_batch`), each slot
decodes on its own cursor (the ragged ``(B,)`` position vector), and
KV is routed through a `KVStore` — dense or paged with the cross-
tenant prefix cache (`serve/kvstore.py`). Page-aware admission
reserves every in-flight request's remaining block growth before
taking new work, so a decode append can always allocate its tail
block.

Admission runs a real ``model.prefill`` per admitted prompt and
migrates the resulting KV into the free slot with the same operators
the disaggregated engine streams through its channel — the colocated
engine is the disaggregated one with a zero-length wire, which is what
makes the two bit-for-bit comparable (tests/test_serve_disagg.py).

The decoupled-analytics hook streams per-step serving stats (tokens/s,
active slots, queue depth) through a `workload_stats` operator — the
paper's Listing-1 pattern applied to an inference fleet.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.sample import sample_last
from repro.obs import registry as _metrics
from repro.obs import trace as _obs
from repro.serve.api import ServeConfig
from repro.serve.kvstore import make_kvstore
from repro.serve.sched import FleetLedger, FleetScheduler

# colocated-engine tracks (obs.trace): process "engine", one thread per
# phase; requests flow-link through these via request_mark. A span whose
# name ends in "_wait" is the host waiting on the chip; every other span
# is host work (dispatch included)
_T_PREFILL = ("engine", "prefill")
_T_DECODE = ("engine", "decode")


def prefill_bucket(n: int, minimum: int = 8, max_len: int | None = None) -> int:
    """Round a prompt length up to a power-of-two bucket so admission
    compiles O(log max_len) prefill programs instead of one per
    distinct length. The length-masked prefill makes the padding
    invisible (exact logits at n-1, zero KV beyond n).

    The doubling clamps at ``max_len``: a prompt near the model's max
    sequence length must bucket AT it, not past it — an over-doubled
    bucket would compile a prefill shape the slot cache cannot hold. A
    prompt longer than ``max_len`` is the caller's bug and raises."""
    if max_len is not None and n > max_len:
        raise ValueError(f"prompt length {n} exceeds max_len {max_len}")
    b = minimum
    while b < n:
        b *= 2
    if max_len is not None:
        b = min(b, max_len)
    return b


def supports_length_masked_prefill(cfg) -> bool:
    """Attention-only LMs can prefill right-padded prompts exactly;
    SSM/hybrid/enc-dec caches cannot rewind past padding."""
    return not (
        getattr(cfg, "ssm_state", 0)
        or getattr(cfg, "hybrid", False)
        or getattr(cfg, "family", "") == "encdec"
    )


class PrefillRunner:
    """Jitted prefill shared by both engines.

    Attention-only LMs go through the power-of-two padded bucket with
    the length-masked prefill; other families compile per distinct
    prompt length. Compilation is keyed on ``(bucket, batch)`` — the
    batch-1 `__call__` and the packed multi-prompt `run_batch` share
    one jitted wrapper whose shape signature carries both dimensions,
    so continuous admission does not recompile per prompt-count beyond
    the first sighting of each (bucket, batch) pair.
    """

    def __init__(self, model, params, max_len: int | None = None):
        self.params = params
        self.max_len = max_len  # bucket cap: migrated KV must fit the slot cache
        self._exact = jax.jit(lambda p, t: model.prefill(p, t)[:2])
        self._masked = jax.jit(lambda p, t, n: model.prefill(p, t, length=n)[:2])
        self._bucketed = supports_length_masked_prefill(model.cfg)

    def __call__(self, prompt: np.ndarray) -> tuple:
        """prompt (n,) int32 -> (last-token logits, per-request cache)."""
        if not self._bucketed:
            return self._exact(self.params, prompt[None, :])
        n = int(prompt.shape[0])
        b = prefill_bucket(n, max_len=self.max_len)
        padded = np.zeros((1, b), prompt.dtype)
        padded[0, :n] = prompt
        return self._masked(self.params, padded, n)

    def run_batch(self, prompts: list) -> tuple:
        """Packed multi-prompt prefill: prompts right-padded to one
        shared bucket, per-row true lengths -> (per-row last-position
        logits (n, 1, V), batched cache with per-row ``pos``). Needs
        the length-masked (ragged) prefill; batch-1 falls back to
        `__call__`'s exact path for other families."""
        if not self._bucketed:
            raise ValueError("packed prefill needs a length-maskable model")
        lens = [int(p.shape[0]) for p in prompts]
        b = prefill_bucket(max(lens), max_len=self.max_len)
        padded = np.zeros((len(prompts), b), prompts[0].dtype)
        for i, p in enumerate(prompts):
            padded[i, : lens[i]] = p
        return self._masked(self.params, padded, jnp.asarray(lens, jnp.int32))


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray  # (prompt_len,) int32
    max_new_tokens: int = 32
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    # tick-clock bookkeeping (time-to-first-token / drain analytics)
    submitted_tick: int = -1
    first_token_tick: int = -1
    done_tick: int = -1
    tenant: str = "default"  # FleetScheduler queue key (traffic.TenantSpec)


def request_block_tokens(kv, req: "Request", max_len: int) -> int:
    """Block tokens ``req`` occupies through completion, net of its
    prefix-cache discount — the page-aware admission price."""
    bs = kv.block_size
    n = min(int(req.prompt.shape[0]) + req.max_new_tokens, max_len)
    covered = kv.covered_tokens(req.prompt, int(req.prompt.shape[0]))
    return (-(-n // bs)) * bs - covered


def page_admission_budget(kv, slots, max_len: int, *, extra_need_tokens: int = 0):
    """(free_tokens, cost_fn) for `FleetScheduler.take`, or
    (None, None) when the store is not page-limited.

    The budget is the pool's free (plus prefix-evictable) block tokens
    minus the growth every in-flight request may still need to finish
    (the admission math of DESIGN.md §12) — reserving growth up front
    is what guarantees a decode append can always allocate its tail
    block. ``extra_need_tokens`` charges work admitted but not yet in a
    slot (the disaggregated engine's prefill rows + handoff queue)."""
    if kv.block_size is None:
        return None, None
    bs = kv.block_size
    reserve = 0
    for i, req in enumerate(slots):
        if req is None:
            continue
        n = int(kv.lens[i])
        target = min(n + req.max_new_tokens - len(req.out_tokens), max_len)
        reserve += (-(-target // bs) - (-(-n // bs))) * bs
    free = max(0, kv.free_tokens() - reserve - extra_need_tokens)
    return free, lambda req: request_block_tokens(kv, req, max_len)


@dataclasses.dataclass
class EngineConfig(ServeConfig):
    max_batch: int = 8


class Engine:
    def __init__(self, model, params, cfg: EngineConfig,
                 sched: FleetScheduler | None = None):
        self.model = model
        self.params = params
        self.cfg = cfg
        if cfg.mode == "continuous" and not supports_length_masked_prefill(model.cfg):
            raise ValueError(
                "continuous batching needs an attention-only LM "
                "(ragged per-slot decode cursors)"
            )
        # the ServeFleet queue: default is the FIFO scheduler, which
        # pops in submit order with no budget — the sequence of jitted
        # calls (hence the output bits) is identical to the historic
        # bare-deque path (asserted by tests/test_fleet.py and fig13)
        self.sched = sched if sched is not None else FleetScheduler.fifo()
        self.ledger = FleetLedger()
        self.slots: list[Request | None] = [None] * cfg.max_batch
        self.finished: list[Request] = []
        self._decode = jax.jit(model.decode_step)
        # kernel-path decode (continuous mode): attention reads the KV
        # pool through block tables, no per-step paged_gather; absent
        # for families without a paged decode (SSM/hybrid, enc-dec)
        self._decode_paged = (
            None if model.decode_step_paged is None
            else jax.jit(model.decode_step_paged)
        )
        self._prefill = PrefillRunner(model, params, max_len=cfg.max_len)
        self.kv = make_kvstore(model, cfg.max_batch, cfg.max_len, cfg.kv,
                               ragged=cfg.mode == "continuous")
        self.tokens = jnp.zeros((cfg.max_batch, 1), jnp.int32)
        self.last_logits = None  # (B, 1, V) of the latest decode step
        self.tick = 0
        # rejected submits live on the scheduler (sched.rejected)
        self.stats = {"steps": 0, "tokens_out": 0, "prefills": 0,
                      "prefix_hit_tokens": 0, "prefill_skips": 0}
        self.last_tick: dict = {"prefill_lens": [], "decode_batch": 0}

    @property
    def cache(self) -> dict:
        """The slot KV as a dense cache dict (read view; the paged
        store gathers its block tables)."""
        if self.kv.kind == "dense":
            return self.kv.cache
        return self.kv.view([i for i, s in enumerate(self.slots) if s is not None])

    def submit(self, req: Request) -> bool:
        req.submitted_tick = self.tick
        ok = self.sched.submit(req, now=self.tick)
        # lifecycle span opens HERE and only here: fault retries and
        # resize re-queues go straight to sched.submit, so the one open
        # span survives recovery and closes once in record_done
        if ok and _obs.enabled():
            _obs.request_begin(req.uid, tenant=req.tenant, tick=self.tick,
                               prompt_tokens=int(req.prompt.shape[0]))
        return ok

    def idle(self) -> bool:
        return self.sched.pending() == 0 and all(s is None for s in self.slots)

    # -- page-aware admission budget ---------------------------------------
    def _page_budget(self):
        budget, cost_fn = page_admission_budget(
            self.kv, self.slots, self.cfg.max_len
        )
        if budget is None and self.cfg.mode == "continuous":
            # dense stores aren't page-limited, but they now report an
            # honest free-token count: gate on it with a uniform
            # max_len cost per request. Budget = free_slots * max_len
            # with every candidate priced at max_len admits exactly the
            # same set (in the same order) as the bare max_n gate —
            # both KV modes drive take() through one interface.
            return self.kv.free_tokens(), lambda req: self.cfg.max_len
        return budget, cost_fn

    # -- prefill one request into a free slot ------------------------------------
    def _admit(self) -> None:
        free = [i for i, s in enumerate(self.slots) if s is None]
        # colocated engine: admitted prompts prefill synchronously, so
        # the token budget caps this tick's admitted prompt tokens
        for req in self.sched.take(self.tick, max_n=len(free)):
            slot = free.pop(0)
            self.slots[slot] = req
            # batch-1 prefill, then migrate the per-request cache into
            # the slot (zero-extended to max_len)
            with _obs.span("prefill", _T_PREFILL, uid=req.uid,
                           tokens=int(req.prompt.shape[0])):
                logits, cache1 = self._prefill(req.prompt)
            if _obs.enabled():
                _obs.request_mark(req.uid, "admit", _T_PREFILL, slot=slot)
            self.kv.admit(slot, cache1, int(req.prompt.shape[0]))
            first = sample_last(logits)[0]
            self.tokens = self.tokens.at[slot, 0].set(first)
            self.stats["prefills"] += 1
            self.last_tick["prefill_lens"].append(int(req.prompt.shape[0]))

    def _admit_continuous(self) -> None:
        """Admit into whatever slots are free *right now* — called both
        at the tick head and again after retirement, so a slot freed
        this tick refills this tick. Admitted prompts prefill packed
        (one jitted call), except whole-prompt prefix-cache hits, which
        skip prefill entirely."""
        with _obs.span("engine.admit", _T_PREFILL) as sp:
            taken, cold, hits = self._admit_free_slots()
            sp.set(taken=taken, cold=cold, hits=hits)

    def _admit_free_slots(self) -> tuple[int, int, int]:
        """`_admit_continuous`'s work: (taken, cold, prefix hits)."""
        free = [i for i, s in enumerate(self.slots) if s is None]
        if not free:
            return 0, 0, 0
        with _obs.span("sched.take", _T_PREFILL) as sp:
            budget, cost_fn = self._page_budget()
            # dense stores have no page budget; keep the take() call
            # wire-identical to the pre-paging scheduler interface so
            # PR-1-style scheduler duck types still work
            gate = {} if budget is None else {"free_tokens": budget, "cost_fn": cost_fn}
            taken = self.sched.take(self.tick, max_n=len(free), **gate)
            sp.set(taken=len(taken))
        cold: list[tuple[int, Request]] = []
        for req in taken:
            slot = free.pop(0)
            self.slots[slot] = req
            entry = self.kv.full_hit(req.prompt)
            if entry is not None:
                info = self.kv.admit_from_full(slot, entry, uid=req.uid)
                self.tokens = self.tokens.at[slot, 0].set(entry.first)
                if _obs.enabled():
                    _obs.request_mark(req.uid, "admit:prefix_hit", _T_PREFILL,
                                      slot=slot)
                self.stats["prefill_skips"] += 1
                self.stats["prefix_hit_tokens"] += info["prefix_tokens"]
                self.last_tick["prefix_hit_tokens"] += info["prefix_tokens"]
            else:
                cold.append((slot, req))
        hits = len(taken) - len(cold)
        if not cold:
            return len(taken), 0, hits
        lens = [int(r.prompt.shape[0]) for _, r in cold]
        # times the dispatch of the packed prefill, not its device run
        with _obs.span("engine.prefill", _T_PREFILL, rows=len(cold),
                       tokens=sum(lens),
                       bucket=prefill_bucket(max(lens), max_len=self.cfg.max_len)):
            logits, batch = self._prefill.run_batch([r.prompt for _, r in cold])
        if _obs.enabled():
            for slot, req in cold:
                _obs.request_mark(req.uid, "admit", _T_PREFILL, slot=slot)
        call_nets = []
        for i, (slot, req) in enumerate(cold):
            n = lens[i]
            cache1 = {k: (jnp.int32(n) if k == "pos" else v[:, i : i + 1])
                      for k, v in batch.items()}
            row_logits = logits[i, -1]
            first = sample_last(logits[i : i + 1])[0]
            with _obs.span("engine.first_token_wait", _T_PREFILL, uid=req.uid):
                first_id = int(first)
            info = self.kv.admit(slot, cache1, n, tokens=req.prompt,
                                 logits=row_logits, first=first_id, uid=req.uid)
            self.tokens = self.tokens.at[slot, 0].set(first)
            self.stats["prefills"] += 1
            self.stats["prefix_hit_tokens"] += info["prefix_tokens"]
            self.last_tick["prefix_hit_tokens"] += info["prefix_tokens"]
            # the virtual clock prices the packed call by its bucket
            # and batch; per-request lens let it discount prefix hits
            self.last_tick["prefill_lens"].append(n - info["prefix_tokens"])
            call_nets.append(n - info["prefix_tokens"])
        # one packed jitted call; its clock price is the bucket of the
        # longest *uncovered* suffix (a cache-aware prefill computes
        # only what the prefix cache did not already hold) at this batch
        if max(call_nets) > 0:
            self.last_tick["prefill_calls"].append(
                (prefill_bucket(max(call_nets), max_len=self.cfg.max_len),
                 len(cold)))
        return len(taken), len(cold), hits

    # -- one tick ----------------------------------------------------------
    def step(self) -> None:
        """One engine tick: admit, decode one token for every slot
        (continuous mode re-admits after retirement — same-tick slot
        refill)."""
        if self.cfg.mode == "continuous":
            return self._step_continuous()
        self.last_tick = {"prefill_lens": [], "decode_batch": 0}
        self._admit()
        self.tick += 1
        if all(s is None for s in self.slots):
            return
        with _obs.span("decode", _T_DECODE, tick=self.tick,
                       batch=sum(s is not None for s in self.slots)):
            logits, cache = self._decode(self.params, self.kv.view(), self.tokens)
        self.kv.absorb(cache, [i for i, s in enumerate(self.slots) if s is not None])
        self.last_logits = logits
        next_tok = sample_last(logits)
        next_np = np.asarray(next_tok)
        self.last_tick["decode_batch"] = sum(s is not None for s in self.slots)
        self._retire(next_np)
        self.tokens = next_tok[:, None]
        self.stats["steps"] += 1

    def _step_continuous(self) -> None:
        self.last_tick = {"prefill_lens": [], "prefill_calls": [],
                          "decode_batch": 0, "prefix_hit_tokens": 0}
        with _obs.span("engine.step", _T_DECODE) as step:
            self._admit_continuous()
            self.tick += 1
            active = [i for i, s in enumerate(self.slots) if s is not None]
            step.set(rows=len(active))
            if active:
                self._decode_continuous(active)
            # same-tick insertion: slots retired above refill immediately
            self._admit_continuous()
            if _obs.enabled():
                # per-tick KV stats feed only the trace's kv counter and
                # the registry's kv gauges; readers of the store's state
                # ask `kv.stats` themselves
                kv = self.last_tick["kv"] = self.kv.stats
                _metrics.publish_kv_stats(kv)
                _obs.counter("kv", {k: kv[k] for k in ("blocks_in_use", "live_tokens")
                                    if k in kv}, _T_DECODE)
            self.stats["steps"] += 1

    def _decode_continuous(self, active: list[int]) -> None:
        """Decode one token for every active slot, then retire the
        finished ones."""
        with _obs.span("engine.decode", _T_DECODE, tick=self.tick, rows=len(active)):
            if self._decode_paged is not None:
                # kernel path: decode attends straight into the pool
                # through the block tables; the step returns just its
                # new K/V rows and the store scatters them — no dense
                # view materialized, no whole-cache round trip
                logits, rows_k, rows_v = self._decode_paged(
                    self.params, self.kv.kernel_view(active), self.tokens
                )
                self.kv.absorb_rows(rows_k, rows_v, active)
            else:
                logits, cache = self._decode(self.params, self.kv.view(active),
                                             self.tokens)
                self.kv.absorb(cache, active)
            self.last_logits = logits
            next_tok = sample_last(logits)
        with _obs.span("engine.token_wait", _T_DECODE):
            next_np = np.asarray(next_tok)
        self.last_tick["decode_batch"] = len(active)
        with _obs.span("engine.retire", _T_DECODE) as sp:
            freed = self._retire(next_np)
            for slot in freed:
                self.kv.free(slot)
            sp.set(finished=len(freed))
        self.tokens = next_tok[:, None]

    def _retire(self, next_np: np.ndarray) -> list[int]:
        """Record this tick's token per active slot; finish requests at
        EOS / length. Returns the freed slot indices."""
        freed = []
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            tok = int(next_np[i])
            if req.first_token_tick < 0:
                req.first_token_tick = self.tick
            req.out_tokens.append(tok)
            self.stats["tokens_out"] += 1
            if tok == self.cfg.eos_id or len(req.out_tokens) >= req.max_new_tokens:
                req.done = True
                req.done_tick = self.tick
                self.finished.append(req)
                if _obs.enabled():
                    _obs.request_mark(req.uid, "retire", _T_DECODE, slot=i)
                self.ledger.record_done(req, self.sched.slo(req.tenant), self.tick)
                self.slots[i] = None
                freed.append(i)
        return freed

    def drain(self, max_steps: int = 10_000) -> int:
        """Step until idle; returns the steps taken. Hitting the cap
        with work still queued raises — a scheduling deadlock must be
        loud, not a silently-truncated benchmark."""
        for n in range(max_steps):
            if self.idle():
                return n
            self.step()
        if not self.idle():
            raise RuntimeError(
                f"engine stalled after {max_steps} steps: "
                f"queue={self.sched.pending()} "
                f"slots={sum(s is not None for s in self.slots)}"
            )
        return max_steps

    # pre-PR-6 name, kept as an alias for existing call sites
    run_until_drained = drain

    def workload_sample(self) -> dict:
        """Per-tick analytics payload for the decoupled analytics group."""
        return {
            "active_slots": sum(s is not None for s in self.slots),
            "queue_depth": self.sched.pending(),
            "tokens_out": self.stats["tokens_out"],
        }
