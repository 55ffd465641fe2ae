"""The whole training step's share of the chips' bf16 peak: required
operations per token (causal, unpadded head: ``flops.py``) times the traced
window's tokens per second, over chips times peak."""
from benchmark import flops

NAME, UNIT, SOURCE = "train_step_mfu", "%", "host_clock"
LAYER, MOVES = "train step program", "train_tokens_per_s"


def read(ctx):
    rec, cell = ctx["records"], ctx["cell"]
    rate = len(rec["steps"]) * rec["tokens_per_step"] / rec["window_s"]
    need = flops.train_flops_per_token(cell.config, rec["seq_len"])
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need * rate / (cell.chips * peak)
