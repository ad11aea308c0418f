"""Mean rows of the traced decode steps (the ``rows`` of each
``engine.decode`` span) over the cell's ``max_batch``
(bench/program_spans.py)."""
from bench import program_spans as ps


def read(rec):
    spans = ps.for_run(rec)
    if not spans:
        return None
    return ps.occupancy(spans, rec["lo"], rec["hi"], rec["cell"]["engine"]["max_batch"])
