"""The most token-expert pairs that any ONE held expert got in a dispatch
and expert layer over the mean pairs of a held expert there, both summed
over the traced window's landed dispatches: ``pairs_max`` and
``pairs_held`` of the program's ``serving_decode`` spans, the latter over
the experts held. 100% is an even load; the grouped product's tiles and
an exchange across chips both pay for the fullest expert. Nothing to read
where the program counts neither."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_expert_load_imbalance", "%", "program_counter"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    counts = [s.args for s in program_spans.named(ctx, "serving_decode")
              if "pairs_max" in s.args]
    held = sum(a["pairs_held"] for a in counts)
    if not held:
        return None
    experts = ctx["cell"].config["n_routed_experts"]
    return 100.0 * sum(a["pairs_max"] for a in counts) / (held / experts)
