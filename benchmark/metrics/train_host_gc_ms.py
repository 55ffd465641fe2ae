"""Host time in Python's garbage collection a training step: the seconds of
the program's ``train_gc`` spans that begin in the traced window, over the
``train_batch`` spans that begin there (``offline_host_gc_ms``'s count for
the train engine's loop). Nothing to read where the program has no
collection hook or opens no step span."""
from benchmark import harness

NAME, UNIT, SOURCE = "train_host_gc_ms", "ms", "program_span"
LAYER, MOVES = "train engine", "train_tokens_per_s"


def read(ctx):
    return harness.load_named("metrics", "offline_host_gc_ms").gc_ms_a_step(
        ctx, "train", "train_batch")
