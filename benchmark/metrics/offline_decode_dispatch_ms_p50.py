"""Median duration of the program's ``serving_decode_dispatch`` span over
the traced window: the call of the decode program until it returns, which
is what handing it its arguments (every leaf of the weights and the pools)
costs on the host. Nothing to read where the program opens no such span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_decode_dispatch_ms_p50", "ms", "program_span"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    return program_spans.median_ms(
        s.seconds for s in program_spans.named(ctx,
                                               "serving_decode_dispatch"))
