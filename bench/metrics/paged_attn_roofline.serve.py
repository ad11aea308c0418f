"""The paged_attention kernel's share of its roofline: the least time
the chip needs for the keys and values the traced decode steps attend
(bench/kernels/paged_attention.py, from the attended lengths recorded
per step), over the kernel's summed device time. The kernel is the
Pallas call inside the decode-step program."""
from bench import trace_names as tn
from bench.kernels import paged_attention as pa
from bench.tracereduce import time_where, within


def read(rec):
    dev = tn.device0(rec)
    decode = [m for m in dev["modules"] if tn.matches(tn.DECODE)(m[0])]
    kernels = within([op for op in dev["ops"] if tn.is_kernel(op[0])], decode,
                     rec["lo"], rec["hi"])
    t, n = time_where(kernels, lambda _: True, rec["lo"], rec["hi"])
    if not n:
        return None
    flops = nbytes = 0.0
    for tick in rec["traced"]["ticks"]:
        f, b = pa.cost(rec["config"], tick["attended"])
        flops, nbytes = flops + f, nbytes + b
    if not flops:
        return None
    return 100.0 * pa.roofline_seconds(flops, nbytes, rec["peaks"]) / (t / 1e9)
