"""Median, over the traced window's steps, of the program's
``serving_step`` span less the ``serving_decode_wait`` inside it: all the
host does in a step, hidden behind the device or not. Nothing to read where
the program opens no such span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_step_host_ms_p50", "ms", "program_span"
LAYER, MOVES = "serve engine", "serve_tokens_per_s"


def read(ctx):
    return program_spans.median_ms(
        step.seconds - sum(w.seconds
                           for w in step.find("serving_decode_wait"))
        for step in program_spans.named(ctx, "serving_step"))
