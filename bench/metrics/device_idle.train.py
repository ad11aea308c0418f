"""Share of the traced window in which a chip does no work, on the chip
that works least: no operation runs, or only collectives do (waiting in
a transfer is idle). In the decoupled step that is the reduce chip,
which skips forward and backward and waits for the gradient stream."""
from bench import trace_names as tn
from bench.tracereduce import working_ns


def read(rec):
    span = rec["hi"] - rec["lo"]
    return 100.0 * max(1.0 - working_ns(d, rec["lo"], rec["hi"]) / span for d in tn.devices(rec))
