"""The held experts' grouped products in decode against their roofline: the
least time the chip could take for what the traced window's landed decode
dispatches needed of them (``experts_touched`` and ``decode_pairs_held``
of the program's ``serving_decode`` spans: the weights of every held
expert that took a pair in a dispatch's expert layer, read once, and the
pairs' rows; ``flops_experts.expert_decode_cost``, about 18 operations a
byte at 18 pairs an expert, so HBM binds) over the summed device time of
the operations named ``expert_decode`` (the decode step's two grouped
products an expert layer; a prefill chunk's are ``expert_prefill``).
Nothing to read where the trace shows no such name or the program counts
no touched experts."""
from benchmark import flops, flops_experts, program_spans

NAME, UNIT, SOURCE = "offline_expert_decode_roofline", "%", "device_trace"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    taken = sum(s for name, s in ctx["trace"].ops.items()
                if name.split(".")[0] == "expert_decode")
    spans = [s.args for s in program_spans.named(ctx, "serving_decode")
             if "experts_touched" in s.args]
    touched = sum(a["experts_touched"] for a in spans)
    if taken <= 0.0 or not touched:
        return None
    cost = flops_experts.expert_decode_cost(
        ctx["cell"].config, touched,
        sum(a["decode_pairs_held"] for a in spans))
    least = flops.roofline_seconds(cost, flops.peaks(ctx["device_kind"]))
    return 100.0 * least["seconds"] / taken
