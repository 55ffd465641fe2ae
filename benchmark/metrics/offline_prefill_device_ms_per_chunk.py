"""Device time of prefill a chunk: the summed durations of the programs
whose name holds ``prefill`` on the trace's ``XLA Modules`` line, over the
program's ``serving_prefill`` spans in the traced window (one a chunk,
whatever call carried it). Where a call carries one chunk this is the
program's mean time; where it carries several it is what each chunk costs
of the calls, pad rows included. Nothing to read where the trace shows no
such program or the program opens no chunk span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = ("offline_prefill_device_ms_per_chunk", "ms",
                      "device_trace")
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    taken = [s for name, runs in ctx["trace"].programs.items()
             if "prefill" in name for s in runs]
    chunks = program_spans.named(ctx, "serving_prefill")
    if not taken or not chunks:
        return None
    return 1e3 * sum(taken) / len(chunks)
