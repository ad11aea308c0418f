"""Admission's host time per decode step: the ``engine.admit`` spans
less their KV store (``kv.*``), ``engine.prefill`` and ``*_wait``
descendants, so the scheduler's take with its page budget, the prefix
lookups and the slot bookkeeping (bench/program_spans.py)."""
from bench import program_spans as ps


def read(rec):
    return ps.per_step_ms(rec, ps.admit_ns)
