"""The program's own spans of a traced run, cut to the measured window.

While a profiler session runs, ``deepspeed_tpu.telemetry.trace_span`` is
live: every span the program opens (``serving_step`` and what lies inside
it, ``train_batch`` and its four phases) is kept in the process's tracer as
a Chrome-trace event on ``perf_counter_ns``, the clock of the harness's own
``Spans.rows``. This is the one reduction of those events that the
``program_span`` readers share: the events that begin inside the ``window``
row, clipped to it, nested by containment on the thread that recorded them.
A program that opens no such span (the tracer of a commit before PR 26 is
off in a benchmark run) gives an empty list, and each reader then reads
nothing.
"""

import dataclasses
import statistics


@dataclasses.dataclass
class Span:
    name: str
    start: float        # seconds on time.perf_counter
    end: float
    args: dict
    children: list = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def self_seconds(self) -> float:
        """The span's duration less the part its child spans cover
        (children of one parent on one thread never overlap)."""
        return self.seconds - sum(c.seconds for c in self.children)

    def find(self, *names) -> list:
        """Every span below this one that bears one of ``names``, by
        start."""
        out = []
        for c in self.children:
            if c.name in names:
                out.append(c)
            out.extend(c.find(*names))
        return out


def window(ctx):
    """(start, end) of the measured window on ``perf_counter``; nothing
    where the run had none (an untraced run reads no per-layer metric)."""
    for name, t0, t1 in ctx["spans"]:
        if name == "window":
            return t0, t1
    return None


def program_events() -> list:
    """The complete ("X") events the program's tracer holds."""
    from deepspeed_tpu.telemetry import get_tracer
    return [e for e in get_tracer().events() if e.get("ph") == "X"]


def nest(events, t0, t1) -> list:
    """Chrome-trace events (``ts`` / ``dur`` in microseconds) that begin in
    [t0, t1) as :class:`Span` trees, ends clipped to ``t1`` and to the
    parent's end, by start. A span lies under the innermost span of its
    thread that was open when it began; of two that begin in the same
    microsecond the longer is the parent, and of two equal ones the one
    recorded later (a parent is recorded when it closes, after its
    children)."""
    threads = {}
    for i, e in enumerate(events):
        start = e["ts"] * 1e-6
        if t0 <= start < t1:
            threads.setdefault((e.get("pid"), e.get("tid")), []).append(
                (e["ts"], -e["dur"], -i, e))
    roots = []
    for rows in threads.values():
        open_spans = []
        for ts, neg_dur, _, e in sorted(rows, key=lambda r: r[:3]):
            span = Span(e["name"], ts * 1e-6,
                        min((ts - neg_dur) * 1e-6, t1), e.get("args") or {})
            while open_spans and span.start >= open_spans[-1].end:
                open_spans.pop()
            if open_spans:
                span.end = min(span.end, open_spans[-1].end)
                open_spans[-1].children.append(span)
            else:
                roots.append(span)
            open_spans.append(span)
    return sorted(roots, key=lambda s: s.start)


def named(ctx, name) -> list:
    """Every span of the traced window that bears ``name``, at whatever
    depth, each with the spans that lie inside it; by start."""
    w = window(ctx)
    if w is None:
        return []
    top = Span("window", w[0], w[1], {}, nest(program_events(), *w))
    return top.find(name)


def median_ms(seconds):
    """Median of some durations in milliseconds; nothing of none."""
    seconds = list(seconds)
    return 1e3 * statistics.median(seconds) if seconds else None
