"""The KV store's host time per decode step: self time of the ``kv.*``
spans other than ``*_wait`` (block tables built and uploaded, argument
uploads, refcounts): the host half of what ``kv_scatter_ms.serve``
times on the chip (bench/program_spans.py)."""
from bench import program_spans as ps


def read(rec):
    return ps.per_step_ms(rec, ps.kv_host_ns)
