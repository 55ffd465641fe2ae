"""Host time in Python's garbage collection a serving step: the seconds of
the program's ``serving_gc`` spans that begin in the traced window, over
the ``serving_step`` spans that begin there. A collection stops the host
loop whole, so one longer than the step's slack reaches the chip (and the
innermost-span rule then names its gap ``serving_gc``). Nothing to read
where the program has no collection hook (a program before it) or opens
no step span."""
from benchmark import program_spans

NAME, UNIT, SOURCE = "offline_host_gc_ms", "ms", "program_span"
LAYER, MOVES = "serve engine", "serve_tokens_per_s"


def gc_ms_a_step(ctx, loop, step):
    """Milliseconds of ``<loop>_gc`` spans a ``step`` span in the window;
    no collection in a window with steps reads 0."""
    from deepspeed_tpu.telemetry import tracer
    steps = program_spans.named(ctx, step)
    if not steps or not hasattr(tracer, "watch_gc"):
        return None
    paused = sum(s.seconds for s in program_spans.named(ctx, f"{loop}_gc"))
    return 1e3 * paused / len(steps)


def read(ctx):
    return gc_ms_a_step(ctx, "serving", "serving_step")
