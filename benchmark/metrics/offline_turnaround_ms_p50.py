"""Median, over the traced window's steps, of the time from the end of one
step's ``serving_decode_wait`` to the start of the next step's first
``serving_prefill`` or ``serving_decode_dispatch``. The wait is the host's
one sync: when it returns the device has finished and nothing is queued, so
until the next dispatch the device idles on the host: delivery, gauges and
ticks, the runner's ``collect`` and ``top_up``, the scheduler, the decode
inputs. Nothing to read where the program opens no such span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_turnaround_ms_p50", "ms", "program_span"
LAYER, MOVES = "serve engine", "serve_tokens_per_s"


def read(ctx):
    steps = program_spans.named(ctx, "serving_step")
    gaps = []
    for step, after in zip(steps, steps[1:]):
        waits = step.find("serving_decode_wait")
        dispatches = after.find("serving_prefill", "serving_decode_dispatch")
        if waits and dispatches:
            gaps.append(dispatches[0].start - waits[-1].end)
    return program_spans.median_ms(gaps)
