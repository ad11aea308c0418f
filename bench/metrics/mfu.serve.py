"""Operations the traced steps' prefills and decodes require
(bench/flops.py: true prompt lengths, attended lengths) over the traced
window times the chip's peak."""
from bench import flops


def read(rec):
    cfg = rec["config"]
    total = 0.0
    for tick in rec["traced"]["ticks"]:
        total += sum(flops.decode_token(cfg, a) for a in tick["attended"])
        total += sum(flops.prefill(cfg, n) for n in tick.get("prefill_lens", []))
    span_s = (rec["hi"] - rec["lo"]) / 1e9
    return 100.0 * total / (span_s * rec["peaks"]["bf16_flops_per_s"])
