"""Compilations (cache loads included) that JAX reported between the
window's opening and its close. There should be none."""
NAME, UNIT, SOURCE = "compiles_in_window.train", "count", "program_counter"
LAYER, MOVES = "entry", "train_tokens_per_s"


def read(ctx):
    return ctx["records"]["compiles_in_window"]
