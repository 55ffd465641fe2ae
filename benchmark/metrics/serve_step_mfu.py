"""The whole serving step's share of the chip's bf16 peak: required
operations of the traced window's prompt and output tokens
(``flops.serve_flops``: causal, unpadded head, a prompt charged with its
first output token) over the window and the peak."""
from benchmark import flops

NAME, UNIT, SOURCE = "serve_step_mfu", "%", "host_clock"
LAYER, MOVES = "serve step", "serve_tokens_per_s"


def read(ctx):
    rec, cell = ctx["records"], ctx["cell"]
    need = sum(flops.serve_flops(cell.config, rec["shape_of"][rid][0],
                                 rec["at_open"].get(rid, 0), last)
               for rid, last in rec["at_close"].items())
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / rec["window_s"] / (cell.chips * peak)
