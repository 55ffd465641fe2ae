"""Median host-clock time of one ``train_batch``, synced on its loss, over
the traced window's steps."""
import statistics

NAME, UNIT, SOURCE = "train_step_ms_p50", "ms", "host_clock"
LAYER, MOVES = "train engine", "train_tokens_per_s"


def read(ctx):
    steps = ctx["records"]["steps"]
    return 1e3 * statistics.median(t1 - t0 for t0, t1 in steps)
