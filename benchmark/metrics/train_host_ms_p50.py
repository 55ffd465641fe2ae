"""Median duration of the program's ``train_batch`` span over the traced
window: the traced run syncs on the loss outside it, so it is all host work
(the next batch, its placement, the dispatch, the book-keeping after it).
Against ``train_step_ms_p50`` it says how far the host is from setting the
pace. Nothing to read where the program opens no such span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "train_host_ms_p50", "ms", "program_span"
LAYER, MOVES = "train engine", "train_tokens_per_s"


def read(ctx):
    return program_spans.median_ms(
        s.seconds for s in program_spans.named(ctx, "train_batch"))
