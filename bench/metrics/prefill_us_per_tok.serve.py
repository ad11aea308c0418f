"""Device time of the packed-prefill programs over the prompt tokens
they prefilled (true lengths, padding not counted), in the traced part
of the window."""
from bench import trace_names as tn
from bench.tracereduce import time_where


def read(rec):
    t, n = time_where(tn.device0(rec)["modules"], tn.matches(tn.PREFILL), rec["lo"], rec["hi"])
    tokens = sum(tk["prefill_tokens"] for tk in rec["traced"]["ticks"])
    return t / 1e3 / tokens if n and tokens else None
