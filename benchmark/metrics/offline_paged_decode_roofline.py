"""The paged decode attention's share of its roofline, heads in lanes (the
GPT-2 cells): the least time the chip could take for the cached tokens
that the traced window's decode dispatches had to read (``blocks_needed``
of the program's ``serving_decode`` spans x the block's tokens x the
layers; a cached token of one layer is a key row and a value row of
``n_embd`` values, 2 B each and read once at the UNPADDED width, since pad
lanes are the implementation's and not the algorithm's, and ``4 x n_embd``
operations: one operation a byte, so HBM binds and
``flops.roofline_seconds`` says so) over the summed device time of the
operations named ``paged_decode``. The count is the program's counter and
the model's shape: the same work whatever the kernel does inside. Nothing
to read where the trace shows no such name or the spans count no blocks."""
from benchmark import flops, program_spans

NAME, UNIT, SOURCE = "offline_paged_decode_roofline", "%", "device_trace"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"

BLOCK_SIZE = 16     # the served cache's block (the package default, which
                    # the cells' deployments do not change)


def paged_decode_cost(config, blocks: int, block_size: int) -> dict:
    """Operations and HBM bytes of one decode step's attention over
    ``blocks`` cache blocks a layer, all ``n_layer`` layers: scores and
    the weighted sum are ``2 x n_embd`` operations a cached token each,
    against its key and its value row in bfloat16."""
    tokens = blocks * block_size * config["n_layer"]
    return {"flops": float(tokens * 4 * config["n_embd"]),
            "bytes": float(tokens * 2 * config["n_embd"] * 2)}


def read(ctx):
    taken = sum(s for name, s in ctx["trace"].ops.items()
                if name.split(".")[0] == "paged_decode")
    blocks = sum(s.args.get("blocks_needed", 0)
                 for s in program_spans.named(ctx, "serving_decode"))
    if taken <= 0.0 or not blocks:
        return None
    cost = paged_decode_cost(ctx["cell"].config, blocks, BLOCK_SIZE)
    least = flops.roofline_seconds(cost, flops.peaks(ctx["device_kind"]))
    return 100.0 * least["seconds"] / taken
