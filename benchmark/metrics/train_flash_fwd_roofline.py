"""The forward flash kernel's share of its roofline: the least time for 2
of the causal call's 7 matrix products and 4 of its 12 tensor passes over
the summed device time of the operations named ``flash_fwd``. Nothing to
read where the trace shows no such name."""
from benchmark import flash_parts

NAME, UNIT, SOURCE = "train_flash_fwd_roofline", "%", "device_trace"
LAYER, MOVES = "train kernels", "train_tokens_per_s"


def read(ctx):
    return flash_parts.roofline_share(ctx, ("flash_fwd",), 2, 4)
