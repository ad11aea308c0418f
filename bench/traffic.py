"""The one traffic generator: reads a mix file ``bench/traffic/<mix>.json``
and makes that mix's inputs from ``--seed``.

Every seed gets the same sizes (quantiles of the mix's length
distributions) in another order, with token ids drawn from the seed:
two seeds differ in order and content, not in the amount of work. The
lognormal arithmetic is that of the program's traffic models
(``rng.lognormal(log(median), sigma)`` rounded and clipped); here it is
applied to quantiles instead of draws.

Kinds of mix:

* ``closed_loop`` (serving): ``clients`` callers, each sending its next
  request as soon as its previous one completes; greedy decoding. Each
  request has a prompt and a number of tokens to generate; the first
  ``tokens`` of a prompt may be one of ``count`` shared prefixes
  (``shared_prefix``).
* ``train_batches`` (training): a ring of ``ring`` distinct batches of
  ``rows_per_chip`` rows per chip, ``seq`` tokens each.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose); any whole seed."""
    return np.random.default_rng([int(seed) & (2**63 - 1), int(seed) >> 63, stream])


def lengths_at(spec: dict, q: np.ndarray) -> np.ndarray:
    """Lengths at quantiles ``q`` of a clipped lognormal, rounded to the
    spec's ``quantum`` (default 1)."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
    x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    step = int(spec.get("quantum", 1))
    x = np.rint(x / step) * step
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def spread_quantiles(n: int, start: float, step: float) -> np.ndarray:
    """``n`` quantiles walking the unit interval by an irrational step:
    any run of consecutive ones covers it evenly (a low-discrepancy
    sequence), so every stretch of the stream holds about the same
    sizes, whatever ``start`` the seed gives."""
    return (start + step * np.arange(n) + 0.5 / n) % 1.0


@dataclasses.dataclass
class RequestSpec:
    uid: int
    prompt: np.ndarray  # (n,) int32
    max_new_tokens: int


def closed_loop_requests(mix: dict, vocab: int, seed: int) -> list[RequestSpec]:
    """The mix's request stream for ``seed``, in the order clients take
    it. Prompt and output lengths walk their distributions' quantiles
    by two unrelated irrational steps from starts drawn from the seed,
    so any window of a few dozen requests holds about the same work for
    every seed. The first ``clients`` requests start mid-generation (a
    uniform share of their output left), so completions are spread
    from the first tick as they are in a long-running server."""
    n = int(mix["requests"])
    start = rng_for(seed, 1).uniform(0.0, 1.0, 2)
    prompts = lengths_at(mix["prompt"], spread_quantiles(n, start[0], (5 ** 0.5 - 1) / 2))
    outs = lengths_at(mix["output"], spread_quantiles(n, start[1], 2 ** 0.5 - 1))
    sp = mix.get("shared_prefix") or {"count": 0, "tokens": 0}
    tok_rng = rng_for(seed, 2)
    shared = tok_rng.integers(0, vocab, (max(sp["count"], 1), sp["tokens"]), dtype=np.int64)
    which = tok_rng.integers(0, max(sp["count"], 1), n)
    phase = rng_for(seed, 3).uniform(0.0, 1.0, n)
    reqs = []
    for i in range(n):
        p = tok_rng.integers(0, vocab, int(prompts[i]), dtype=np.int64)
        if sp["count"] and prompts[i] >= sp["tokens"]:
            p[: sp["tokens"]] = shared[which[i]]
        m = int(outs[i])
        if i < mix["clients"]:
            m = max(1, int(np.ceil(phase[i] * m)))
        reqs.append(RequestSpec(uid=i, prompt=p.astype(np.int32), max_new_tokens=m))
    return reqs


def train_batch(mix: dict, vocab: int, seed: int, index: int, n_rows: int) -> dict:
    """Batch ``index`` of the ring: ``n_rows`` rows of ``seq`` tokens,
    next-token labels, every token counted."""
    rng = rng_for(seed, 100 + int(index))
    seq = int(mix["seq"])
    toks = rng.integers(0, vocab, (n_rows, seq + 1), dtype=np.int64).astype(np.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:],
            "mask": np.ones((n_rows, seq), np.float32)}
