"""Host time of the serving engine per decode step: its ``engine.step``
spans less their ``*_wait`` descendants (the host waiting on the chip),
read from the program's own spans in the traced part of the window
(bench/program_spans.py)."""
from bench import program_spans as ps


def read(rec):
    return ps.per_step_ms(rec, ps.engine_host_ns)
