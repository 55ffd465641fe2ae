"""The latent decode attention's share of its roofline: the least time the
chip could take for the latent rows that the traced window's decode
dispatches visited (``blocks_visited`` of the program's ``serving_decode``
spans x the block's tokens x the layers; a row is 576 values = 1,152 B
read once and ``2 x 128 x (576 + 512)`` operations, 242 operations a byte,
the chip's ridge: ``flops.roofline_seconds`` takes the larger bound) over
the summed device time of the operations named ``paged_decode``. Nothing to
read where the trace shows no such name or the program counts no
blocks."""
from benchmark import flops, flops_mla_moe, program_spans

NAME, UNIT, SOURCE = "offline_latent_decode_roofline", "%", "device_trace"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"

BLOCK_SIZE = 16     # the served cache's block (the package default, which
                    # the cell's deployment does not change)


def read(ctx):
    taken = sum(s for name, s in ctx["trace"].ops.items()
                if name.split(".")[0] == "paged_decode")
    blocks = sum(s.args.get("blocks_visited", 0)
                 for s in program_spans.named(ctx, "serving_decode"))
    if taken <= 0.0 or not blocks:
        return None
    config = ctx["cell"].config
    cost = flops_mla_moe.latent_decode_cost(
        config, blocks * config["num_hidden_layers"], BLOCK_SIZE)
    least = flops.roofline_seconds(cost, flops.peaks(ctx["device_kind"]))
    return 100.0 * least["seconds"] / taken
