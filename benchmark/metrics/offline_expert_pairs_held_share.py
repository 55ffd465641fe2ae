"""Token-expert choices whose expert this chip holds over all the choices
that the router made, summed over the traced window's landed dispatches:
the program's own counts, carried by its ``serving_decode`` spans as
``pairs_held`` and ``pairs_absent`` (the decode dispatch that landed in the
span and the prefill chunks queued before it). held / published experts
(6.25% at 16 of 256) when the router keeps its width. Nothing to read
where the program counts neither."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_expert_pairs_held_share", "%", "program_counter"
LAYER, MOVES = "serve programs", "serve_tokens_per_s"


def read(ctx):
    counts = [s.args for s in program_spans.named(ctx, "serving_decode")
              if "pairs_held" in s.args]
    total = sum(a["pairs_held"] + a["pairs_absent"] for a in counts)
    if not total:
        return None
    return 100.0 * sum(a["pairs_held"] for a in counts) / total
