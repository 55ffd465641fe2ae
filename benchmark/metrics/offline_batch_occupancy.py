"""Slots that decoded or prefilled over ``max_batch``, over the traced
window's steps, counted by the runner around ``step()``."""
NAME, UNIT, SOURCE = "offline_batch_occupancy", "%", "program_counter"
LAYER, MOVES = "serve engine", "serve_tokens_per_s"


def read(ctx):
    rec = ctx["records"]
    worked = sum(chunks + decodes for _, _, chunks, decodes in rec["steps"])
    return 100.0 * worked / (len(rec["steps"]) * rec["max_batch"])
