"""Prefill chunks a call of the prefill program carries, over the traced
window: the ``chunks`` of the program's ``serving_prefill_dispatch`` spans
summed, over how many such spans there are. 1 where each chunk is a
program of its own; up to the program's rows where a step's chunks share a
call (the last call of a step carries what is left). Nothing to read where
the program opens no such span (one that dispatches every chunk alone
opens none)."""
from benchmark import program_spans

NAME, UNIT, SOURCE = ("offline_prefill_chunks_per_dispatch", "chunks",
                      "program_span")
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    calls = program_spans.named(ctx, "serving_prefill_dispatch")
    if not calls:
        return None
    return sum(s.args.get("chunks", 0) for s in calls) / len(calls)
