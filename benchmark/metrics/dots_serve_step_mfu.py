"""The whole serving step's share of the chip's bf16 peak for a decoder
with latent attention and routed experts: required operations of the traced
window's prompt and output tokens (``flops_mla_moe.serve_flops``: weights
outside the routed experts, the expected share of the experts held, the
expanded attention, a head where a logit is needed) over the window and
the peak. The share of the whole step that bounds later claims in the
cell."""
from benchmark import flops, flops_mla_moe

NAME, UNIT, SOURCE = "dots_serve_step_mfu", "%", "host_clock"
LAYER, MOVES = "serve step", "serve_tokens_per_s"


def read(ctx):
    rec, cell = ctx["records"], ctx["cell"]
    need = sum(flops_mla_moe.serve_flops(
        cell.config, rec["shape_of"][rid][0], rec["at_open"].get(rid, 0), last)
        for rid, last in rec["at_close"].items())
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / rec["window_s"] / (cell.chips * peak)
