"""Median device duration of the prefill-chunk program on the trace's ``XLA
Modules`` line."""
NAME, UNIT, SOURCE = "offline_prefill_program_ms", "ms", "device_trace"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    return ctx["trace"].program_median_ms("prefill")
