"""The backward flash kernels' share of their roofline: the least time for
5 of the causal call's 7 matrix products and 8 of its 12 tensor passes over
the summed device time of the operations named ``flash_dq`` and
``flash_dkv`` (which between them compute the scores twice: recomputed work
never counts). Nothing to read where the trace shows no such name."""
from benchmark import flash_parts

NAME, UNIT, SOURCE = "train_flash_bwd_roofline", "%", "device_trace"
LAYER, MOVES = "train kernels", "train_tokens_per_s"


def read(ctx):
    return flash_parts.roofline_share(ctx, ("flash_dq", "flash_dkv"), 5, 8)
