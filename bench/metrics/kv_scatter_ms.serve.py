"""Device time of the KV store's programs (row scatter, block fill) per
engine step, in the traced part of the window."""
from bench import trace_names as tn
from bench.tracereduce import time_where


def read(rec):
    dev = tn.device0(rec)
    _, steps = time_where(dev["modules"], tn.matches(tn.DECODE), rec["lo"], rec["hi"])
    t, _ = time_where(dev["modules"], tn.matches(tn.KV_STORE), rec["lo"], rec["hi"])
    return t / steps / 1e6 if steps else None
