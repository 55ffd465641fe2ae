"""From a profiler capture (``*.xplane.pb``) to the few things the metrics
read: device busy and idle time, the longest operations, the idle gaps named
by what the host was doing, program durations.

The host's spans are the harness's own (``bench:<name>``) and the program's
(``deepspeed_tpu.telemetry.trace_span`` writes ``serving_*`` and ``train_*``
annotations into the same capture's ``/host:`` plane while the profiler
runs): one clock, so a gap is named by the innermost span over its longest
part, ``serving_decode_wait`` or ``serving_deliver`` rather than ``step``.

The reduction follows ``deepspeed_tpu/telemetry/step_anatomy.py`` as repaired
in PR 21: only the ``XLA Ops`` line of a ``/device:TPU:n`` plane is an
operation lane, only ``XLA Modules`` holds whole programs, and an operation
is named by the instruction name at the head of its HLO text. It reads the
capture with ``jax.profiler.ProfileData`` and nothing else. Name-based
categories are not attempted: fusions are ``fusion.N`` on the chip.
"""

import bisect
import dataclasses
import re
import statistics
from pathlib import Path

# names of the program's own spans in the capture's host plane (PERF.md,
# Layers): whatever begins so is a span beside the harness's ``bench:`` ones
PROGRAM_SPANS = ("serving_", "train_", "fused_step")

# The device keeps its trace in a buffer of its own and records nothing once
# that is full: 4.72-4.78 M operations on the v5e's ``XLA Ops`` line, 5.6-10 s
# of a serving cell (my chip runs, PR 31). What ran after that is not idle
# time, it is not in the capture: where the last operation ends this long
# before the window does, the window is cut there (``Reduced.cut_s``). The
# cells' traced windows are sized to stay clear of it.
TRACE_ENDS_EARLY_NS = 50_000_000

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
_PROGRAM = re.compile(r"^([\w.\-<>]+?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Reduced:
    window_s: float             # the traced window, as far as the trace goes
    cut_s: float                # of the window, after the device trace ended
    busy_s: float               # union of op intervals, mean over chips
    ops: dict                   # instruction name -> seconds (mean over chips)
    programs: dict              # program name -> [seconds] on chip 0
    gaps: list                  # [(host span name, seconds)] on chip 0
    chips: int

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program_median_ms(self, key: str):
        """Median device duration of the programs whose name holds ``key``;
        nothing where the trace shows none."""
        runs = [s for name, v in self.programs.items() if key in name
                for s in v]
        return 1e3 * statistics.median(runs) if runs else None

    def breakdown(self, n=10) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1])[:n]
        gaps = sorted(self.gaps, key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in gaps]}


def find(trace_dir: Path) -> Path:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"the profiler wrote no capture under "
                                f"{trace_dir}")
    return files[-1]


def instruction_name(text: str) -> str:
    m = _INSTRUCTION.match(text)
    return m.group(1) if m else text[:48]


def program_name(text: str) -> str:
    m = _PROGRAM.match(text)
    return m.group(1) if m else text


def _union(intervals):
    """Total length and the merged list of [start, end] intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def _host_spans(data, prefix, program=PROGRAM_SPANS):
    """(name, start, end) of the harness's spans, ``prefix`` taken off, and
    of the program's, under their own names."""
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    name = ev.name[len(prefix):]
                elif ev.name.startswith(program):
                    name = ev.name
                else:
                    continue
                spans.append((name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return spans


def innermost(spans) -> list:
    """The spans as one line of time: [(start, end, name)] in order, each
    stretch under the shortest span that covers it; stretches that no span
    covers are left out."""
    edges = sorted({t for _, s, e in spans for t in (s, e)})
    by_start = sorted(spans, key=lambda sp: sp[1])
    line, open_spans, nxt = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while nxt < len(by_start) and by_start[nxt][1] <= a:
            open_spans.append(by_start[nxt])
            nxt += 1
        open_spans = [sp for sp in open_spans if sp[2] > a]
        if open_spans:
            name = min(open_spans, key=lambda sp: sp[2] - sp[1])[0]
            if line and line[-1][2] == name and line[-1][1] == a:
                line[-1] = (line[-1][0], b, name)
            else:
                line.append((a, b, name))
    return line


def name_gap(a, b, line) -> str:
    """The span of ``line`` (:func:`innermost`) over the longest part of the
    gap [a, b]: a gap of a few milliseconds runs from the end of one span
    into the next, and its midpoint may lie in neither of the two that hold
    most of it."""
    under = {}
    covered = 0
    i = max(bisect.bisect_right(line, a, key=lambda part: part[0]) - 1, 0)
    while i < len(line) and line[i][0] < b:
        part = min(b, line[i][1]) - max(a, line[i][0])
        if part > 0:
            under[line[i][2]] = under.get(line[i][2], 0) + part
            covered += part
        i += 1
    under["outside-spans"] = (b - a) - covered
    return max(under, key=under.get)


def reduce(path, chips: int, span_prefix: str = "bench:") -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans = _host_spans(data, span_prefix)
    windows = [(s, e) for name, s, e in spans if name == "window"]
    inner = [sp for sp in spans if sp[0] != "window"]

    # with the window known, an event outside it is dropped before its name
    # (the whole HLO text of the operation) is made: the capture holds the
    # settling steps too, and what ran until the profiler stopped
    w0, w1 = windows[0] if windows else (float("-inf"), float("inf"))

    def inside(events):
        out = []
        for ev in events:
            s = ev.start_ns
            e = s + ev.duration_ns
            if e > w0 and s < w1:
                out.append((ev.name, s, e))
        return out

    lanes = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lane = lanes.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == "XLA Ops":
                lane["ops"] = inside(line.events)
            elif line.name == "XLA Modules":
                lane["modules"] = inside(line.events)
    if not lanes:
        raise ValueError(f"{path}: no /device:TPU:n plane in the capture")
    if not windows:             # a capture without the benchmark's spans
        every = [t for lane in lanes.values() for _, s, e in lane["ops"]
                 for t in (s, e)]
        w0, w1 = min(every), max(every)

    used = sorted(lanes)[:chips]
    traced_to = min(max((e for _, _, e in lanes[d]["ops"]), default=w0)
                    for d in used)
    if traced_to <= w0:
        raise ValueError(f"{path}: no operation of the device lies in the "
                         f"traced window: the device's trace ended before "
                         f"the window opened")
    cut = 0
    if traced_to < w1 - TRACE_ENDS_EARLY_NS:
        cut, w1 = w1 - traced_to, traced_to
    busy, ops, named = 0.0, {}, {}
    for d in used:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in lanes[d]["ops"]]
        total, merged = _union([(s, e) for _, s, e in clipped])
        busy += total
        for text, s, e in clipped:
            if text not in named:       # some thousands of texts, millions
                named[text] = instruction_name(text)
            name = named[text]
            ops[name] = ops.get(name, 0.0) + (e - s)
        if d == used[0]:
            first_merged = merged
    n = len(used)
    line = innermost(inner)
    gaps, cursor = [], w0
    for s, e in first_merged + [[w1, w1]]:
        if s > cursor:
            gaps.append((name_gap(cursor, s, line), (s - cursor) * 1e-9))
        cursor = max(cursor, e)
    programs = {}
    for text, s, e in lanes[used[0]]["modules"]:
        if not cut or e <= w1:      # a program the cut leaves whole
            programs.setdefault(program_name(text), []).append((e - s) * 1e-9)
    return Reduced(window_s=(w1 - w0) * 1e-9, cut_s=cut * 1e-9,
                   busy_s=busy * 1e-9 / n,
                   ops={k: v * 1e-9 / n for k, v in ops.items()},
                   programs=programs, gaps=gaps, chips=n)


if __name__ == "__main__":          # python -m benchmark.trace <capture>
    import json
    import sys
    r = reduce(sys.argv[1], chips=1)
    print(json.dumps({"window_s": r.window_s, "cut_s": r.cut_s,
                      "busy_s": r.busy_s,
                      "idle_share": r.idle_share,
                      "programs": {k: [len(v), sum(v)]
                                   for k, v in r.programs.items()},
                      **r.breakdown()}, indent=1))
