"""Compilations (cache loads included) that JAX reported between the
window's opening and its close, plus new signatures that the server's
``compile_stats()`` counted there. There should be none."""
NAME, UNIT, SOURCE = "compiles_in_window.serve", "count", "program_counter"
LAYER, MOVES = "entry", "serve_tokens_per_s"


def read(ctx):
    return ctx["records"]["compiles_in_window"]
