"""Operations the forward and backward passes of the trained tokens
require (bench/flops.py), at the traced window's rate, over all the
cell's chips times the peak: the reduce chip's idle time counts."""
from bench import flops


def read(rec):
    steps = rec["traced_steps"]
    if not steps:
        return None
    per_token = flops.train_token(rec["config"], rec["mix"]["seq"])
    span_s = (rec["hi"] - rec["lo"]) / 1e9
    total = per_token * rec["tokens_per_step"] * steps
    return 100.0 * total / (span_s * rec["chips"] * rec["peaks"]["bf16_flops_per_s"])
