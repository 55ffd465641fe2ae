"""From a profiler capture (``*.xplane.pb``) to the few things the metrics
read: device busy and idle time, the longest operations, the idle gaps named
by what the host was doing, program durations, custom-call time.

The reduction follows ``deepspeed_tpu/telemetry/step_anatomy.py`` as repaired
in PR 21: only the ``XLA Ops`` line of a ``/device:TPU:n`` plane is an
operation lane, only ``XLA Modules`` holds whole programs, and an operation
is named by the instruction name at the head of its HLO text. It reads the
capture with ``jax.profiler.ProfileData`` and nothing else. Name-based
categories are not attempted: fusions are ``fusion.N`` on the chip.
"""

import dataclasses
import re
import statistics
from pathlib import Path

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_INSTRUCTION = re.compile(r"^%?([\w.\-]+)")
_PROGRAM = re.compile(r"^([\w.\-<>]+?)(?:\(\d+\))?$")


@dataclasses.dataclass
class Reduced:
    window_s: float             # the traced window
    busy_s: float               # union of op intervals, mean over chips
    ops: dict                   # instruction name -> seconds (mean over chips)
    custom_call_s: float        # seconds in custom calls (Pallas / Mosaic)
    programs: dict              # program name -> [seconds] on chip 0
    gaps: list                  # [(host span name, seconds)] on chip 0
    idle_under: dict            # host span name -> idle seconds on chip 0
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


def is_custom_call(text: str) -> bool:
    """A Pallas (Mosaic) kernel: XLA's own custom calls (``ConcatBitcast``
    and the like) name another target."""
    return "custom-call(" in text and (
        "custom_call_target" not in text or "tpu_custom_call" in text)


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


def _host_spans(data, prefix):
    spans = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(prefix):
                    spans.append((ev.name[len(prefix):], ev.start_ns,
                                  ev.start_ns + ev.duration_ns))
    return spans


def _name_gap(mid, spans):
    """The shortest host span (the innermost) that covers ``mid``."""
    best = None
    for name, s, e in spans:
        if s <= mid <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "outside-spans"


def reduce(path, chips: int, span_prefix: str = "bench:") -> Reduced:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    spans = _host_spans(data, span_prefix)
    windows = [(s, e) for name, s, e in spans if name == "window"]
    inner = [sp for sp in spans if sp[0] != "window"]

    lanes = {}
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lane = lanes.setdefault(int(m.group(1)), {"ops": [], "modules": []})
        for line in plane.lines:
            if line.name == "XLA Ops":
                lane["ops"] = [(ev.name, ev.start_ns, ev.start_ns
                                + ev.duration_ns) for ev in line.events]
            elif line.name == "XLA Modules":
                lane["modules"] = [(ev.name, ev.start_ns, ev.start_ns
                                    + ev.duration_ns) for ev in line.events]
    if not lanes:
        raise ValueError(f"{path}: no /device:TPU:n plane in the capture")
    if windows:
        w0, w1 = windows[0]
    else:                       # a capture without the benchmark's spans
        every = [t for lane in lanes.values() for _, s, e in lane["ops"]
                 for t in (s, e)]
        w0, w1 = min(every), max(every)

    used = sorted(lanes)[:chips]
    busy, ops, custom, named = 0.0, {}, 0.0, {}
    for d in used:
        clipped = [(n, max(s, w0), min(e, w1)) for n, s, e in lanes[d]["ops"]
                   if e > w0 and s < w1]
        total, merged = _union([(s, e) for _, s, e in clipped])
        busy += total
        for text, s, e in clipped:
            if text not in named:       # some thousands of texts, millions
                named[text] = (instruction_name(text), is_custom_call(text))
            name, is_custom = named[text]
            ops[name] = ops.get(name, 0.0) + (e - s)
            if is_custom:
                custom += e - s
        if d == used[0]:
            first_merged = merged
    n = len(used)
    gaps, idle_under, cursor = [], {}, w0
    for s, e in first_merged + [[w1, w1]]:
        if s > cursor:
            name = _name_gap((cursor + s) / 2.0, inner)
            gaps.append((name, (s - cursor) * 1e-9))
            idle_under[name] = idle_under.get(name, 0.0) + (s - cursor) * 1e-9
        cursor = max(cursor, e)
    programs = {}
    for text, s, e in lanes[used[0]]["modules"]:
        if e > w0 and s < w1:
            programs.setdefault(program_name(text), []).append((e - s) * 1e-9)
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy * 1e-9 / n,
                   ops={k: v * 1e-9 / n for k, v in ops.items()},
                   custom_call_s=custom * 1e-9 / n, programs=programs,
                   gaps=gaps, idle_under=idle_under, chips=n)


if __name__ == "__main__":          # python -m benchmark.trace <capture>
    import json
    import sys
    r = reduce(sys.argv[1], chips=1)
    print(json.dumps({"window_s": r.window_s, "busy_s": r.busy_s,
                      "idle_share": r.idle_share,
                      "custom_call_s": r.custom_call_s,
                      "programs": {k: [len(v), sum(v)]
                                   for k, v in r.programs.items()},
                      **r.breakdown()}, indent=1))
