"""Step anatomy — measured device-time attribution from profiler traces.

Everything the repo previously said about *where device time goes* was a
static prediction (CostExplorer rooflines over the HLO census).  This
module reads back the traces ``jax.profiler`` writes (via the
dependency-free ``telemetry.xplane`` wire parser) and joins the measured
per-op device events to the programs the engine already owns:

* per-op device seconds bucketed into six categories
  (matmul/convolution, collective, scatter/gather, elementwise/fusion,
  host-transfer, idle-gap), with the invariant that category seconds sum
  to the captured device wall time *exactly* (a per-lane coverage sweep
  splits every lane window into busy + idle with no double counting);
* attribution to model modules via HLO ``op_name`` metadata paths
  (``jit(step)/.../h_1/ln_2/mul`` → module ``h_1/ln_2``) and the PR-3
  health-bucket spec names;
* steps delimited by ``TraceAnnotation`` span marks
  (``ds_anatomy_step``) that ``engine.profile_step`` emits;
* measured-vs-predicted rows against CostExplorer's roofline floors
  (drift flagged when > 25%), and a measured collective-overlap fraction
  compared against the census's static schedule positions;
* per-device Chrome-trace lanes that ``fleet.merge_traces`` can join
  with the host tracer's spans.

CLI: ``python -m deepspeed_tpu.telemetry.step_anatomy --render PATH`` /
``--demo [--out PATH]``.
"""

import argparse
import json
import os
import re
import sys
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

ANATOMY_SCHEMA = "deepspeed_tpu.step_anatomy/1"

# annotation name profile_step/profile_window wrap each captured step in
STEP_MARK = "ds_anatomy_step"

BUSY_CATEGORIES = (
    "matmul_convolution",
    "collective",
    "scatter_gather",
    "elementwise_fusion",
    "host_transfer",
)
CATEGORIES = BUSY_CATEGORIES + ("idle_gap",)

_PS = 1e-12  # picoseconds → seconds

# ---------------------------------------------------------------------------
# categorisation
# ---------------------------------------------------------------------------

_COLLECTIVE_TOKENS = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)
_MATMUL_TOKENS = ("dot", "convolution", "conv", "gemm", "einsum", "matmul")
_SCATTER_TOKENS = ("scatter", "gather", "dynamic-slice",
                   "dynamic-update-slice", "select-and-scatter")
_TRANSFER_TOKENS = ("copy", "copy-start", "copy-done", "infeed", "outfeed",
                    "send", "send-done", "recv", "recv-done")

_TOKEN_SPLIT = re.compile(r"[._]")


def _tokens(name: str) -> List[str]:
    """Split an HLO instruction name into match tokens.

    ``bitcast_dot_fusion`` → [bitcast, dot, fusion]; a trailing ``.12``
    suffix drops out as a numeric token.  Hyphenated opcodes
    (``dynamic-update-slice``) stay whole so 'slice' alone can't
    misfire, but we also test the raw name for hyphenated tokens.
    """
    return [t for t in _TOKEN_SPLIT.split(name.lower()) if t]


def categorize(name: str, opcode: Optional[str] = None) -> str:
    """Map an HLO instruction (executor event) to a busy category.

    Uses the real opcode when an HLO op table is available; falls back
    to name heuristics (fusion names embed their root ops:
    ``loop_dot_fusion``, ``dynamic-slice_concatenate_fusion``).  Order
    matters: collectives first (``all-gather`` contains 'gather'),
    transfers before matmul so ``copy`` never misfires.
    """
    probe = (opcode or name).lower()
    toks = set(_tokens(probe))
    for t in _COLLECTIVE_TOKENS:
        if t in probe:
            return "collective"
    hyphen_toks = {t for t in re.split(r"[-._]", probe) if t}
    if (toks | hyphen_toks) & {"copy", "infeed", "outfeed", "send", "recv"}:
        # hyphen split catches async pairs (copy-start / recv-done);
        # collectives already returned above, so 'reduce' etc. can't leak
        return "host_transfer"
    if opcode:
        ol = opcode.lower()
        if ol in ("dot", "convolution"):
            return "matmul_convolution"
        if ol in ("scatter", "gather", "dynamic-slice",
                  "dynamic-update-slice", "select-and-scatter"):
            return "scatter_gather"
        if ol == "fusion":
            # fusion: fall through to the *name* heuristics below
            probe = name.lower()
            toks = set(_tokens(probe))
        elif ol == "custom-call":
            probe = name.lower()
            toks = set(_tokens(probe))
        else:
            return "elementwise_fusion"
    if toks & set(_MATMUL_TOKENS):
        return "matmul_convolution"
    for t in _SCATTER_TOKENS:
        if t in probe:
            return "scatter_gather"
    if toks & {"scatter", "gather"}:
        return "scatter_gather"
    return "elementwise_fusion"


# ---------------------------------------------------------------------------
# HLO op table (join key: instruction name → opcode + op_name metadata)
# ---------------------------------------------------------------------------

_HLO_INSTR = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.-]+)\s*=\s*[^=]*?\s"
    r"(?P<opcode>[\w-]+)\(")
_HLO_OPNAME = re.compile(r'op_name="(?P<op>[^"]*)"')
_WRAPPER = re.compile(r"^(jit|pjit|jvp|vjp|vmap|transpose|remat|custom_jvp|"
                      r"custom_vjp|checkpoint|named)\(.*\)$")


def hlo_op_table(hlo_text: str) -> Dict[str, Tuple[str, str]]:
    """Parse HLO text into {instruction_name: (opcode, op_name)}.

    The profiler's executor events are named by HLO instruction name
    (``dot.4``, ``broadcast_maximum_fusion``); the compiled module's
    text carries each instruction's opcode and its ``op_name`` metadata
    path — the join that turns raw timings into model-module
    attribution.
    """
    table: Dict[str, Tuple[str, str]] = {}
    for line in hlo_text.splitlines():
        if "=" not in line:
            continue
        m = _HLO_INSTR.match(line)
        if not m:
            continue
        om = _HLO_OPNAME.search(line)
        table[m.group("name")] = (m.group("opcode"),
                                  om.group("op") if om else "")
    return table


def module_from_op_name(op_name: str) -> str:
    """Reduce an ``op_name`` metadata path to its model-module path.

    ``jit(step)/jit(main)/transpose(jvp(GPT2LMHeadModel))/h_1/ln_2/mul``
    → ``h_1/ln_2`` (tracing wrappers stripped, trailing primitive
    dropped).  Empty string when nothing module-like remains.
    """
    if not op_name:
        return ""
    parts = [p for p in op_name.split("/") if p and not _WRAPPER.match(p)]
    if len(parts) >= 2:
        parts = parts[:-1]          # drop the primitive (mul, dot_general)
    return "/".join(parts)


# ---------------------------------------------------------------------------
# event model + extraction from a parsed XSpace
# ---------------------------------------------------------------------------

@dataclass
class LaneEvent:
    name: str
    start_ps: int
    end_ps: int


# The op lane of an accelerator plane. A ``/device:TPU:n`` plane also
# carries 'Steps', 'XLA Modules' (one event per program run), 'Async XLA
# Ops' (start->done spans that OVERLAP the ops) and 'TC Overlay' lines;
# counting those as lanes multiplies the device wall and books every op
# two or three times (seen on a v5e, jax 0.9 / libtpu 0.0.34).
DEVICE_OP_LINE = "XLA Ops"

# Host executor lines interleave each op with bookkeeping events
# ('end: <op>', ThreadpoolListener::*, Rendezvous) since jax 0.9, so ops
# are 30-45% of such a line's events; the python thread, which also runs
# small programs inline, sits under 1%.
_HOST_LANE_MIN_OP_SHARE = 0.1


def _instruction_name(event_name: str) -> str:
    """The HLO instruction name of a device-plane event. The TPU plane
    names an op by its full HLO text (``%fusion.3 = f32[8]{0} fusion(...),
    kind=kLoop``); the join key is the bare name (``fusion.3``), which is
    also what host executor lines already carry."""
    m = _HLO_INSTR.match(event_name)
    return m.group("name") if m else event_name


def extract_events(space, step_mark: str = STEP_MARK):
    """Pull (steps, lanes) out of a parsed XSpace.

    Device lanes are the ``XLA Ops`` line of each ``/device:`` plane, or
    — CPU jax runs XLA:CPU executors on host threads (``tf_XLAEigen`` /
    ``tf_XLAPjRtCpuClient`` lines) — host-plane lines where at least a
    tenth of the events carry an ``hlo_op`` stat; only those events are
    taken. Steps come from *step_mark* annotation events anywhere in the
    trace.

    Returns ``(steps, lanes)`` where steps is
    ``[(label, start_ps, end_ps)]`` and lanes is
    ``{lane_name: [LaneEvent, ...]}`` with absolute-ps timestamps
    (line timestamp_ns · 1000 + offset) and events named by HLO
    instruction.
    """
    steps: List[Tuple[object, int, int]] = []
    lanes: Dict[str, List[LaneEvent]] = {}
    for plane in space.planes:
        is_device = plane.name.startswith("/device:")
        for line in plane.lines:
            if not line.events:
                continue
            base = line.timestamp_ns * 1000
            op_line = is_device and (line.display_name or line.name) \
                == DEVICE_OP_LINE
            hlo_events = []
            for ev in line.events:
                name = plane.event_name(ev)
                if name == step_mark:
                    stats = plane.event_stats(ev)
                    label = stats.get("step")
                    start = base + ev.offset_ps
                    steps.append((label, start, start + ev.duration_ps))
                elif op_line or (not is_device
                                 and "hlo_op" in plane.event_stats(ev)):
                    hlo_events.append(LaneEvent(
                        _instruction_name(name), base + ev.offset_ps,
                        base + ev.offset_ps + ev.duration_ps))
            if not hlo_events:
                continue
            if not is_device and len(hlo_events) < \
                    _HOST_LANE_MIN_OP_SHARE * len(line.events):
                continue    # the python thread's few inline programs
            lane = f"{plane.name}/{line.display_name or line.name}"
            lanes.setdefault(lane, []).extend(hlo_events)
    steps.sort(key=lambda s: s[1])
    return steps, lanes


# ---------------------------------------------------------------------------
# core attribution
# ---------------------------------------------------------------------------

def _merge_intervals(ivals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(ivals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _overlap_with(ivals: List[Tuple[int, int]], s: int, e: int) -> int:
    """Length of [s,e) ∩ (merged, sorted) *ivals*."""
    import bisect
    total = 0
    i = bisect.bisect_left(ivals, (s,)) - 1
    i = max(0, i)
    while i < len(ivals) and ivals[i][0] < e:
        total += max(0, min(e, ivals[i][1]) - max(s, ivals[i][0]))
        i += 1
    return total


def analyze_events(steps, lanes, op_table=None, bucket_names=None,
                   predicted_floors=None, schedule_positions=None,
                   top_k: int = 12):
    """Join device-lane events to categories/modules; build the report.

    ``steps``: [(label, start_ps, end_ps)] capture windows (empty →
    one window spanning all events).  ``lanes``: {name: [LaneEvent]}.
    ``op_table``: {instr_name: (opcode, op_name)} from ``hlo_op_table``.
    ``predicted_floors``: CostExplorer ``bound_floors_s`` dict
    (per-step seconds, values may be None on hosts without chip specs).
    """
    op_table = op_table or {}
    bucket_names = list(bucket_names or [])
    windows = [(lbl, s, e) for lbl, s, e in steps if e > s]
    if not windows:
        lo = min((ev.start_ps for evs in lanes.values() for ev in evs),
                 default=0)
        hi = max((ev.end_ps for evs in lanes.values() for ev in evs),
                 default=0)
        if hi > lo:
            windows = [(None, lo, hi)]

    cat_ps = {c: 0 for c in CATEGORIES}
    per_op: Dict[str, dict] = {}
    module_ps: Dict[str, Dict[str, int]] = {c: {} for c in BUSY_CATEGORIES}
    lane_rows = []
    step_rows = {i: {"step": lbl, "span_ps": 0, "busy_ps": 0}
                 for i, (lbl, _, _) in enumerate(windows)}
    collective_ivals: List[Tuple[int, int]] = []
    other_ivals: List[Tuple[int, int]] = []
    device_wall_ps = 0

    def _resolve(name):
        entry = op_table.get(name)
        if entry is None and name.endswith("..."):  # truncated display names
            entry = None
        opcode, op_name = entry if entry else (None, "")
        return categorize(name, opcode), module_from_op_name(op_name)

    resolve_cache: Dict[str, Tuple[str, str]] = {}

    for lane_name in sorted(lanes):
        events = sorted(lanes[lane_name], key=lambda ev: ev.start_ps)
        lane_busy = 0
        lane_n = 0
        for wi, (lbl, ws, we) in enumerate(windows):
            span = we - ws
            device_wall_ps += span
            step_rows[wi]["span_ps"] += span
            coverage = ws       # high-water mark: no double counting when
            busy = 0            # pool threads re-report overlapping ops
            for ev in events:
                if ev.end_ps <= ws or ev.start_ps >= we:
                    continue
                contrib = (min(ev.end_ps, we)
                           - max(ev.start_ps, ws, coverage))
                if contrib <= 0:
                    # fully shadowed by an earlier event — still record
                    # the op's presence for counts/overlap, zero seconds
                    contrib = 0
                cached = resolve_cache.get(ev.name)
                if cached is None:
                    cached = resolve_cache[ev.name] = _resolve(ev.name)
                cat, module = cached
                cat_ps[cat] += contrib
                busy += contrib
                rec = per_op.setdefault(
                    ev.name, {"name": ev.name, "category": cat,
                              "module": module, "total_ps": 0, "count": 0})
                rec["total_ps"] += contrib
                rec["count"] += 1
                if module:
                    module_ps[cat][module] = (
                        module_ps[cat].get(module, 0) + contrib)
                cs, ce = max(ev.start_ps, ws), min(ev.end_ps, we)
                (collective_ivals if cat == "collective"
                 else other_ivals).append((cs, ce))
                coverage = max(coverage, min(ev.end_ps, we))
                lane_n += 1
            cat_ps["idle_gap"] += span - busy
            step_rows[wi]["busy_ps"] += busy
            lane_busy += busy
        lane_rows.append({"name": lane_name, "busy_s": lane_busy * _PS,
                          "events": lane_n})

    # ------------------------------------------------ collective overlap
    comp_union = _merge_intervals(other_ivals)
    coll_union = _merge_intervals(collective_ivals)
    coll_total = sum(e - s for s, e in coll_union)
    hidden = sum(_overlap_with(comp_union, s, e) for s, e in coll_union)
    overlap = {
        "collective_s": coll_total * _PS,
        "hidden_behind_compute_s": hidden * _PS,
        "exposed_s": (coll_total - hidden) * _PS,
        "overlap_fraction": (hidden / coll_total) if coll_total else None,
        "census_schedule_positions": schedule_positions,
    }

    # ------------------------------------------- measured vs predicted
    n_steps = len(windows)
    busy_non_coll_ps = sum(cat_ps[c] for c in BUSY_CATEGORIES
                           if c not in ("collective", "host_transfer"))
    measured_by = {
        "compute": busy_non_coll_ps * _PS,
        "memory": busy_non_coll_ps * _PS,
        "comm": cat_ps["collective"] * _PS,
    }
    mvp = []
    for cat in sorted(set(predicted_floors or {"compute", "memory", "comm"})
                      | set(measured_by)):
        floor = (predicted_floors or {}).get(cat)
        predicted = (floor * n_steps) if isinstance(floor, (int, float)) \
            else None
        measured = measured_by.get(cat)
        drift = ((measured / predicted) - 1.0) if (
            predicted and measured is not None) else None
        mvp.append({
            "category": cat,
            "predicted_s": predicted,
            "measured_s": measured,
            "drift": drift,
            "flagged": bool(drift is not None and abs(drift) > 0.25),
        })

    # ----------------------------------------------- module attribution
    attribution = {}
    for cat in BUSY_CATEGORIES:
        rows = sorted(module_ps[cat].items(), key=lambda kv: -kv[1])[:top_k]
        total = cat_ps[cat] or 1
        attribution[cat] = [
            {"module": mod, "seconds": ps * _PS, "share": ps / total,
             "bucket": _match_bucket(mod, bucket_names)}
            for mod, ps in rows]

    top_ops = sorted(per_op.values(), key=lambda r: -r["total_ps"])[:top_k]
    top_ops = [{"name": r["name"], "category": r["category"],
                "module": r["module"], "seconds": r["total_ps"] * _PS,
                "count": r["count"]} for r in top_ops]

    steps_out = []
    for i in range(len(windows)):
        row = step_rows[i]
        steps_out.append({
            "step": row["step"],
            "span_s": row["span_ps"] * _PS,
            "busy_s": row["busy_ps"] * _PS,
            "idle_s": (row["span_ps"] - row["busy_ps"]) * _PS,
        })

    return {
        "schema": ANATOMY_SCHEMA,
        "captured_steps": len(windows),
        "device_wall_s": device_wall_ps * _PS,
        "categories_s": {c: cat_ps[c] * _PS for c in CATEGORIES},
        "category_fractions": {
            c: (cat_ps[c] / device_wall_ps) if device_wall_ps else 0.0
            for c in CATEGORIES},
        "steps": steps_out,
        "lanes": lane_rows,
        "top_ops": top_ops,
        "module_attribution": attribution,
        "collective_overlap": overlap,
        "measured_vs_predicted": mvp,
        "ops_joined_to_hlo": sum(1 for r in per_op.values()
                                 if r["name"] in op_table),
        "ops_total": len(per_op),
        "notes": [],
    }


def _match_bucket(module: str, bucket_names: Sequence[str]) -> Optional[str]:
    """Join a module path to a PR-3 health-bucket spec name (best
    effort: the bucket whose name shares the module's deepest path
    component)."""
    if not module or not bucket_names:
        return None
    tail = module.split("/")[-1]
    for b in bucket_names:
        if module in b or b in module:
            return b
    for b in bucket_names:
        if tail and tail in b:
            return b
    return None


# ---------------------------------------------------------------------------
# trace-dir driver
# ---------------------------------------------------------------------------

def summarize_capture(trace_dir, op_table=None, bucket_names=None,
                      predicted_floors=None, schedule_positions=None,
                      step_mark: str = STEP_MARK):
    """Parse the newest ``.xplane.pb`` under *trace_dir* and attribute
    it.  Returns the report dict, or ``None`` when no parseable capture
    exists (caller treats that as 'profiler wrote nothing')."""
    from deepspeed_tpu.telemetry import xplane
    files = xplane.find_xplane_files(trace_dir)
    if not files:
        return None
    path = files[0]
    try:
        space = xplane.parse_xspace_file(path)
    except (OSError, xplane.XplaneParseError) as exc:
        return {"schema": ANATOMY_SCHEMA, "error": str(exc),
                "source": {"trace": path}}
    steps, lanes = extract_events(space, step_mark=step_mark)
    report = analyze_events(
        steps, lanes, op_table=op_table, bucket_names=bucket_names,
        predicted_floors=predicted_floors,
        schedule_positions=schedule_positions)
    report["source"] = {
        "trace": path,
        "hostnames": space.hostnames,
        "planes": [p.name for p in space.planes],
        "step_mark": step_mark,
        "marked_steps": len(steps),
    }
    if not steps:
        report["notes"].append(
            "no step annotations found — whole capture treated as one "
            "window")
    return report


# ---------------------------------------------------------------------------
# Chrome-trace device lanes
# ---------------------------------------------------------------------------

def device_trace_events(lanes, process_label="xplane device lanes"):
    """Render extracted lanes as Chrome-trace events (ts/dur in µs,
    capture-relative) on registry-allocated tids, ready for
    ``fleet.merge_traces``.  Timestamps are capture-relative — profiler
    and host-tracer clocks share no epoch, so these merge as their own
    process lane rather than interleaving with host spans."""
    from deepspeed_tpu.telemetry.tracer import allocate_lane_tid
    pid = os.getpid()
    events = [{"name": "process_name", "ph": "M", "pid": pid,
               "args": {"name": process_label}}]
    t0 = min((ev.start_ps for evs in lanes.values() for ev in evs),
             default=0)
    for lane_name in sorted(lanes):
        tid = allocate_lane_tid(("xplane", lane_name))
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": lane_name}})
        for ev in lanes[lane_name]:
            events.append({
                "name": ev.name, "ph": "X",
                "ts": (ev.start_ps - t0) / 1e6,
                "dur": (ev.end_ps - ev.start_ps) / 1e6,
                "pid": pid, "tid": tid})
    return events


def write_device_trace(out_path, lanes, process_label="xplane device lanes"):
    """Write lanes as a standalone Chrome-trace JSON file; returns the
    path (input format for ``fleet.merge_traces``)."""
    doc = {"traceEvents": device_trace_events(lanes, process_label),
           "displayTimeUnit": "ms"}
    d = os.path.dirname(out_path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = out_path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, out_path)
    return out_path


# ---------------------------------------------------------------------------
# report IO + rendering
# ---------------------------------------------------------------------------

def write_report(report, path):
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1, allow_nan=False, default=repr)
    os.replace(tmp, path)
    return path


def render(report):
    """Human-readable rendering of a STEP_ANATOMY.json dict."""
    lines = []
    if report.get("error"):
        lines.append(f"step anatomy: PARSE ERROR — {report['error']}")
        return "\n".join(lines)
    wall = report.get("device_wall_s", 0.0)
    lines.append(
        f"step anatomy: {report.get('captured_steps', 0)} step(s), "
        f"device wall {wall * 1e3:.2f} ms across "
        f"{len(report.get('lanes', []))} lane(s)")
    cats = report.get("categories_s", {})
    fr = report.get("category_fractions", {})
    for cat in CATEGORIES:
        if cat in cats:
            lines.append(f"  {cat:20s} {cats[cat] * 1e3:10.3f} ms  "
                         f"({fr.get(cat, 0.0):6.1%})")
    ov = report.get("collective_overlap") or {}
    if ov.get("collective_s"):
        frac = ov.get("overlap_fraction")
        lines.append(
            f"  collective overlap: {ov['collective_s'] * 1e3:.3f} ms "
            f"total, {ov.get('hidden_behind_compute_s', 0) * 1e3:.3f} ms "
            f"hidden" + (f" ({frac:.0%})" if frac is not None else ""))
    for row in report.get("measured_vs_predicted", []):
        pred = row.get("predicted_s")
        meas = row.get("measured_s")
        drift = row.get("drift")
        lines.append(
            "  {}{:8s} predicted {} measured {}{}".format(
                "! " if row.get("flagged") else "  ",
                row.get("category", "?"),
                f"{pred * 1e3:9.3f} ms" if pred is not None
                else "      (n/a)",
                f"{meas * 1e3:9.3f} ms" if meas is not None
                else "      (n/a)",
                f"  drift {drift:+.0%}" if drift is not None else ""))
    for op in report.get("top_ops", [])[:8]:
        lines.append(
            f"  top op {op['name']:40s} {op['seconds'] * 1e3:9.3f} ms "
            f"[{op['category']}]"
            + (f" <- {op['module']}" if op.get("module") else ""))
    att = (report.get("module_attribution") or {}).get(
        "matmul_convolution") or []
    for row in att[:5]:
        lines.append(
            f"  matmul module {row['module']:35s} "
            f"{row['seconds'] * 1e3:9.3f} ms ({row['share']:.0%})"
            + (f" [bucket {row['bucket']}]" if row.get("bucket") else ""))
    for note in report.get("notes", []):
        lines.append(f"  note: {note}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# demo (synthetic capture exercising every category + the full schema)
# ---------------------------------------------------------------------------

def _demo_report():
    """Build a deterministic synthetic anatomy: 3 steps × 2 lanes with
    every category represented and op_name-based module attribution —
    exercises exactly the schema the engine writes."""
    op_table = {}
    lanes = {"demo/device:0": [], "demo/device:1": []}
    steps = []
    us = 1_000_000  # 1 µs in ps
    t = 0
    for s in range(3):
        start = t
        for lane_i, lane in enumerate(sorted(lanes)):
            lt = start
            plan = [
                ("dot.%d" % (s * 10 + lane_i), "dot",
                 "jit(train_step)/transpose(jvp(DemoNet))/h_0/attn/"
                 "dot_general", 300),
                ("loop_dot_fusion.%d" % s, "fusion",
                 "jit(train_step)/jvp(DemoNet)/h_1/mlp/dot_general", 200),
                ("all-reduce.%d" % s, "all-reduce",
                 "jit(train_step)/all_reduce", 150),
                ("dynamic-update-slice.%d" % s, "dynamic-update-slice",
                 "jit(train_step)/h_0/cache/dynamic_update_slice", 60),
                ("copy.%d" % (s * 10 + lane_i), "copy", "", 40),
                ("broadcast_maximum_fusion.%d" % s, "fusion",
                 "jit(train_step)/jvp(DemoNet)/h_0/attn/softmax/max", 120),
            ]
            for name, opcode, op_name, dur_us in plan:
                op_table[name] = (opcode, op_name)
                lanes[lane].append(
                    LaneEvent(name, lt, lt + dur_us * us))
                lt += dur_us * us
            # deliberate idle tail so idle_gap is non-zero
            lt += 80 * us
        steps.append((s, start, lt))
        t = lt
    report = analyze_events(
        steps, lanes, op_table=op_table,
        bucket_names=["h_0/attn", "h_1/mlp", "embeddings"],
        predicted_floors={"compute": 1.3e-3, "memory": 0.9e-3,
                          "comm": 0.2e-3},
        schedule_positions={"interleaved": 1, "trailing": 0})
    report["source"] = {"trace": "(synthetic demo)", "hostnames": [],
                        "planes": ["demo"], "step_mark": STEP_MARK,
                        "marked_steps": 3}
    report["notes"].append(
        "demo-mode synthetic events — run engine.profile_step(n) on a "
        "real engine for measured numbers")
    return report


# --------------------------------------------------------------------- CLI

def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m deepspeed_tpu.telemetry.step_anatomy",
        description="Render or generate step-anatomy reports.")
    ap.add_argument("--render", metavar="PATH",
                    help="render a STEP_ANATOMY.json report, or analyze a "
                         "profiler trace directory / .xplane.pb file")
    ap.add_argument("--demo", action="store_true",
                    help="emit a synthetic demo report")
    ap.add_argument("--out", metavar="PATH", default=None,
                    help="also write the report JSON here")
    args = ap.parse_args(argv)
    if not args.render and not args.demo:
        ap.print_help()
        return 2
    if args.demo:
        report = _demo_report()
    else:
        path = args.render
        if os.path.isdir(path):
            report = summarize_capture(path)
            if report is None:
                print(f"no .xplane.pb files under {path}", file=sys.stderr)
                return 1
        elif path.endswith(".pb"):
            from deepspeed_tpu.telemetry import xplane
            space = xplane.parse_xspace_file(path)
            steps, lanes = extract_events(space)
            report = analyze_events(steps, lanes)
            report["source"] = {"trace": path, "hostnames": space.hostnames,
                                "planes": [p.name for p in space.planes],
                                "step_mark": STEP_MARK,
                                "marked_steps": len(steps)}
        else:
            with open(path) as f:
                report = json.load(f)
    if args.out:
        write_report(report, args.out)
    print(render(report))
    return 0


if __name__ == "__main__":
    sys.exit(main())
