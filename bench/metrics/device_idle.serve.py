"""Share of the traced window in which no operation ran on the chip."""
from bench import trace_names as tn
from bench.tracereduce import busy_ns


def read(rec):
    span = rec["hi"] - rec["lo"]
    return 100.0 * (1.0 - busy_ns(tn.device0(rec), rec["lo"], rec["hi"]) / span)
