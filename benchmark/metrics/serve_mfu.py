"""The whole serving step's share of the chip's bf16 peak, counted by the
cell's own reference: required operations of the traced window's prompt
and output tokens (``serve_flops`` of ``reference/<reference>.py``, the
module the configuration's ``reference`` key names) over the window and
the peak. The share of the whole step that bounds later claims in the
cell; a reference without ``serve_flops`` gives nothing to read."""
from benchmark import flops, harness

NAME, UNIT, SOURCE = "serve_mfu", "%", "host_clock"
LAYER, MOVES = "serve step", "serve_tokens_per_s"


def read(ctx):
    rec, cell = ctx["records"], ctx["cell"]
    ref = harness.load_named("reference", cell.config["reference"])
    if not hasattr(ref, "serve_flops"):
        return None
    need = sum(ref.serve_flops(cell.config, rec["shape_of"][rid][0],
                               rec["at_open"].get(rid, 0), last)
               for rid, last in rec["at_close"].items())
    peak = flops.peaks(ctx["device_kind"])["bf16_flops_per_s"]
    return 100.0 * need / rec["window_s"] / (cell.chips * peak)
