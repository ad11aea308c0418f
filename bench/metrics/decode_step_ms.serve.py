"""Device time per call of the decode-step program, in the traced part
of the window."""
from bench import trace_names as tn
from bench.tracereduce import time_where


def read(rec):
    t, n = time_where(tn.device0(rec)["modules"], tn.matches(tn.DECODE), rec["lo"], rec["hi"])
    return t / n / 1e6 if n else None
