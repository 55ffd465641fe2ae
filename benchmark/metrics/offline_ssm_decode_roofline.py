"""The decode state update's share of its roofline: the least time the
chip could take for the slot-layer states that the traced window's decode
dispatches moved on (``state_rows`` of the program's ``serving_decode``
spans; each a float32 state read once and written once, unpadded, and 5
operations an element: ``ssm_decode_cost`` of the cell's reference, about
0.6 operations a byte, so HBM binds and ``flops.roofline_seconds`` says
so) over the summed device time of the operations named ``ssm_decode``.
Nothing to read where the trace shows no such name or the program counts
no states."""
from benchmark import flops, harness, program_spans

NAME, UNIT, SOURCE = "offline_ssm_decode_roofline", "%", "device_trace"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    taken = sum(s for name, s in ctx["trace"].ops.items()
                if name.split(".")[0] == "ssm_decode")
    states = sum(s.args.get("state_rows", 0)
                 for s in program_spans.named(ctx, "serving_decode"))
    if taken <= 0.0 or not states:
        return None
    config = ctx["cell"].config
    cost = harness.load_named("reference", config["reference"]) \
        .ssm_decode_cost(config, states)
    least = flops.roofline_seconds(cost, flops.peaks(ctx["device_kind"]))
    return 100.0 * least["seconds"] / taken
