"""KV blocks that hold the decoding slots' tokens over the blocks the paged
loop gathers for them (``max_batch`` rows times a trip count set by the
longest resident sequence), summed over the traced window's decode
dispatches: the program's own counts, carried by its ``serving_decode``
spans as ``blocks_needed`` and ``blocks_visited``. Nothing to read where
the program counts neither."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_paged_block_use", "%", "program_counter"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    counts = [s.args for s in program_spans.named(ctx, "serving_decode")
              if "blocks_visited" in s.args]
    visited = sum(a["blocks_visited"] for a in counts)
    if not visited:
        return None
    return 100.0 * sum(a["blocks_needed"] for a in counts) / visited
