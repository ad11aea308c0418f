"""Per train step, the time collective operations run on a chip while
no other operation does; the worst chip."""
from bench import trace_names as tn
from bench.tracereduce import exposed_collective_ns


def read(rec):
    steps = rec["traced_steps"]
    if not steps:
        return None
    worst = max(exposed_collective_ns(d, rec["lo"], rec["hi"]) for d in tn.devices(rec))
    return worst / steps / 1e6
