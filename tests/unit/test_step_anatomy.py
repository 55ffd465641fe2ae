"""Step-anatomy join tests: categorisation, attribution, the exact
sum-to-wall invariant, collective overlap, measured-vs-predicted drift,
the ledger's capture post-processing, the lane-tid registry, the CLI —
and the e2e acceptance run: ``engine.profile_step`` on a real CPU-jax
engine must write a STEP_ANATOMY.json whose categories sum to the
captured device wall within 1% while adding ZERO train-step compiles.
"""

import ast
import json
import os
import shutil

import pytest

import deepspeed_tpu
from deepspeed_tpu.models.simple import SimpleModel, sample_batch
from deepspeed_tpu.telemetry import ledger as ledger_mod
from deepspeed_tpu.telemetry import step_anatomy as sa
from deepspeed_tpu.telemetry.step_anatomy import (BUSY_CATEGORIES,
                                                  CATEGORIES, LaneEvent,
                                                  analyze_events, categorize,
                                                  device_trace_events,
                                                  hlo_op_table,
                                                  module_from_op_name,
                                                  summarize_capture)
from deepspeed_tpu.telemetry.tracer import (_LANE_TID_BASE, _reset_lane_tids,
                                            allocate_lane_tid)

FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                       "tiny_capture.xplane.pb")
TPU_FIXTURE = os.path.join(os.path.dirname(__file__), "data",
                           "tiny_tpu_capture.xplane.pb")

_PS_S = 1e-12


def _sum_close(report, rel=1e-9):
    total = sum(report["categories_s"].values())
    wall = report["device_wall_s"]
    assert wall >= 0
    assert abs(total - wall) <= rel * max(wall, 1e-12), (
        f"categories sum {total} != device wall {wall}")


# ---------------------------------------------------------------------------
# categorisation
# ---------------------------------------------------------------------------

class TestCategorize:
    @pytest.mark.parametrize("name,opcode,want", [
        ("dot.4", "dot", "matmul_convolution"),
        ("convolution.1", "convolution", "matmul_convolution"),
        ("loop_dot_fusion.2", "fusion", "matmul_convolution"),
        ("all-reduce.1", "all-reduce", "collective"),
        ("all-gather.3", "all-gather", "collective"),      # not 'gather'
        ("all-reduce-start.1", "all-reduce-start", "collective"),
        ("reduce-scatter.2", "reduce-scatter", "collective"),
        ("gather.3", "gather", "scatter_gather"),
        ("scatter.9", "scatter", "scatter_gather"),
        ("dynamic-update-slice.1", "dynamic-update-slice",
         "scatter_gather"),
        ("dynamic-slice_concatenate_fusion", "fusion", "scatter_gather"),
        ("copy.2", "copy", "host_transfer"),
        ("copy-start.1", "copy-start", "host_transfer"),
        ("infeed.0", "infeed", "host_transfer"),
        ("broadcast_maximum_fusion.4", "fusion", "elementwise_fusion"),
        ("add.1", "add", "elementwise_fusion"),
        ("exponential.7", "exponential", "elementwise_fusion"),
    ])
    def test_with_opcode(self, name, opcode, want):
        assert categorize(name, opcode) == want

    @pytest.mark.parametrize("name,want", [
        ("all-reduce.1", "collective"),        # collectives before gather
        ("loop_dot_fusion.1", "matmul_convolution"),
        ("copy.5", "host_transfer"),
        ("gather.2", "scatter_gather"),
        ("broadcast_add_fusion", "elementwise_fusion"),
        ("totally_unknown_thing.3", "elementwise_fusion"),
    ])
    def test_name_only_fallback(self, name, want):
        assert categorize(name) == want


HLO_SNIPPET = """\
HloModule jit_train_step

ENTRY main {
  %p0 = f32[8,32]{1,0} parameter(0)
  %dot.1 = f32[8,32]{1,0} dot(%p0, %p0), metadata={op_name="jit(train_step)/transpose(jvp(SimpleModel))/Dense_0/dot_general" source_file="x.py"}
  loop_add_fusion = f32[8,32]{1,0} fusion(%dot.1), kind=kLoop, metadata={op_name="jit(train_step)/jvp(SimpleModel)/Dense_1/add"}
  ROOT %all-reduce.2 = f32[8,32]{1,0} all-reduce(loop_add_fusion), replica_groups={}, metadata={op_name="jit(train_step)/all_reduce"}
}
"""


class TestHloJoin:
    def test_hlo_op_table(self):
        table = hlo_op_table(HLO_SNIPPET)
        assert table["dot.1"] == (
            "dot", "jit(train_step)/transpose(jvp(SimpleModel))/"
                   "Dense_0/dot_general")
        assert table["loop_add_fusion"] == (
            "fusion", "jit(train_step)/jvp(SimpleModel)/Dense_1/add")
        assert table["all-reduce.2"][0] == "all-reduce"
        assert "p0" in table          # parameters parse too

    @pytest.mark.parametrize("op_name,want", [
        ("jit(train_step)/transpose(jvp(GPT2))/h_1/ln_2/mul", "h_1/ln_2"),
        ("jit(step)/jvp(SimpleModel)/Dense_0/dot_general", "Dense_0"),
        ("jit(step)/remat(block)/h_0/attn/softmax/max", "h_0/attn/softmax"),
        ("jit(step)/add", "add"),     # nothing module-like above primitive
        ("", ""),
    ])
    def test_module_from_op_name(self, op_name, want):
        assert module_from_op_name(op_name) == want


# ---------------------------------------------------------------------------
# analyze_events (synthetic lanes; times in ps)
# ---------------------------------------------------------------------------

class TestAnalyzeEvents:
    def test_exact_sum_and_bucketing(self):
        lanes = {"dev0": [LaneEvent("dot.1", 0, 300),
                          LaneEvent("all-reduce.1", 300, 500),
                          LaneEvent("copy.1", 500, 550)]}
        rep = analyze_events([(0, 0, 1000)], lanes)
        assert rep["captured_steps"] == 1
        assert rep["device_wall_s"] == pytest.approx(1000 * _PS_S)
        cats = rep["categories_s"]
        assert cats["matmul_convolution"] == pytest.approx(300 * _PS_S)
        assert cats["collective"] == pytest.approx(200 * _PS_S)
        assert cats["host_transfer"] == pytest.approx(50 * _PS_S)
        assert cats["idle_gap"] == pytest.approx(450 * _PS_S)
        _sum_close(rep)
        assert rep["steps"][0]["busy_s"] == pytest.approx(550 * _PS_S)
        assert rep["steps"][0]["idle_s"] == pytest.approx(450 * _PS_S)

    def test_overlapping_events_never_double_count(self):
        # pool executors can re-report overlapping spans on one lane; the
        # coverage sweep books each ps exactly once
        lanes = {"dev0": [LaneEvent("dot.1", 0, 100),
                          LaneEvent("add.1", 50, 150),
                          LaneEvent("mul.1", 60, 90)]}   # fully shadowed
        rep = analyze_events([(0, 0, 200)], lanes)
        busy = sum(rep["categories_s"][c] for c in BUSY_CATEGORIES)
        assert busy == pytest.approx(150 * _PS_S)
        assert rep["categories_s"]["idle_gap"] == pytest.approx(50 * _PS_S)
        _sum_close(rep)
        ops = {o["name"]: o for o in rep["top_ops"]}
        assert ops["mul.1"]["seconds"] == 0.0       # present, zero booked
        assert ops["add.1"]["seconds"] == pytest.approx(50 * _PS_S)

    def test_window_clipping_and_out_of_window_events(self):
        lanes = {"dev0": [LaneEvent("dot.1", 900, 1100),   # clipped to 100
                          LaneEvent("add.1", 5000, 6000)]}  # outside: gone
        rep = analyze_events([(0, 0, 1000)], lanes)
        assert rep["categories_s"]["matmul_convolution"] == \
            pytest.approx(100 * _PS_S)
        assert rep["ops_total"] == 1
        _sum_close(rep)

    def test_multiple_step_windows_delimit(self):
        lanes = {"dev0": [LaneEvent("dot.1", 100, 300),
                          LaneEvent("dot.2", 1100, 1200)]}
        rep = analyze_events([(0, 0, 1000), (1, 1000, 2000)], lanes)
        assert rep["captured_steps"] == 2
        assert [s["busy_s"] for s in rep["steps"]] == \
            pytest.approx([200 * _PS_S, 100 * _PS_S])
        assert rep["device_wall_s"] == pytest.approx(2000 * _PS_S)
        _sum_close(rep)

    def test_no_steps_fall_back_to_full_span(self):
        lanes = {"dev0": [LaneEvent("dot.1", 500, 700)]}
        rep = analyze_events([], lanes)
        assert rep["captured_steps"] == 1
        assert rep["device_wall_s"] == pytest.approx(200 * _PS_S)
        assert rep["categories_s"]["idle_gap"] == 0.0

    @pytest.mark.parametrize("compute_span,want_frac", [
        ((0, 100), 1.0),     # collective fully hidden behind compute
        ((0, 50), 0.5),      # half hidden
        ((200, 300), 0.0),   # fully exposed
    ])
    def test_collective_overlap_fraction(self, compute_span, want_frac):
        lanes = {
            "dev0": [LaneEvent("all-reduce.1", 0, 100)],
            "dev1": [LaneEvent("dot.1", *compute_span)],
        }
        rep = analyze_events([(0, 0, 400)], lanes)
        ov = rep["collective_overlap"]
        assert ov["collective_s"] == pytest.approx(100 * _PS_S)
        assert ov["overlap_fraction"] == pytest.approx(want_frac)
        assert ov["hidden_behind_compute_s"] + ov["exposed_s"] == \
            pytest.approx(ov["collective_s"])

    def test_no_collectives_overlap_is_none(self):
        rep = analyze_events([(0, 0, 100)],
                             {"dev0": [LaneEvent("dot.1", 0, 50)]})
        assert rep["collective_overlap"]["overlap_fraction"] is None

    def test_measured_vs_predicted_drift_flags(self):
        lanes = {"dev0": [LaneEvent("dot.1", 0, 300),
                          LaneEvent("all-reduce.1", 300, 500)]}
        rep = analyze_events(
            [(0, 0, 1000)], lanes,
            predicted_floors={"compute": 300 * _PS_S,   # exact: no flag
                              "comm": 400 * _PS_S,      # -50%: flagged
                              "memory": None})          # no chip spec
        rows = {r["category"]: r for r in rep["measured_vs_predicted"]}
        assert set(rows) == {"compute", "memory", "comm"}
        assert rows["compute"]["drift"] == pytest.approx(0.0)
        assert rows["compute"]["flagged"] is False
        assert rows["comm"]["drift"] == pytest.approx(-0.5)
        assert rows["comm"]["flagged"] is True
        assert rows["memory"]["predicted_s"] is None
        assert rows["memory"]["drift"] is None
        assert rows["memory"]["measured_s"] == pytest.approx(300 * _PS_S)

    def test_rows_present_even_without_floors(self):
        rep = analyze_events([(0, 0, 100)],
                             {"dev0": [LaneEvent("dot.1", 0, 50)]})
        cats = [r["category"] for r in rep["measured_vs_predicted"]]
        assert {"compute", "memory", "comm"} <= set(cats)

    def test_op_table_join_and_bucket_attribution(self):
        table = hlo_op_table(HLO_SNIPPET)
        lanes = {"dev0": [LaneEvent("dot.1", 0, 300),
                          LaneEvent("loop_add_fusion", 300, 400),
                          LaneEvent("mystery.9", 400, 450)]}
        rep = analyze_events([(0, 0, 500)], lanes, op_table=table,
                             bucket_names=["Dense_0", "Dense_1"])
        assert rep["ops_joined_to_hlo"] == 2
        assert rep["ops_total"] == 3
        att = rep["module_attribution"]["matmul_convolution"]
        assert att and att[0]["module"] == "Dense_0"
        assert att[0]["bucket"] == "Dense_0"
        assert att[0]["share"] == pytest.approx(1.0)
        ew = rep["module_attribution"]["elementwise_fusion"]
        assert any(r["module"] == "Dense_1" and r["bucket"] == "Dense_1"
                   for r in ew)

    def test_empty_capture(self):
        rep = analyze_events([], {})
        assert rep["captured_steps"] == 0
        assert rep["device_wall_s"] == 0.0
        assert rep["ops_total"] == 0


# ---------------------------------------------------------------------------
# lane tids + Chrome-trace device lanes (the PR's tracer collision fix)
# ---------------------------------------------------------------------------

class TestLaneTids:
    def test_registry_is_idempotent_and_collision_free(self):
        _reset_lane_tids()
        try:
            a = allocate_lane_tid(("serving", 0))
            b = allocate_lane_tid(("xplane", "/device:TPU:0"))
            c = allocate_lane_tid(("fleet", 0))
            assert allocate_lane_tid(("serving", 0)) == a
            assert len({a, b, c}) == 3, "synthetic lanes collided"
            assert min(a, b, c) >= _LANE_TID_BASE
        finally:
            _reset_lane_tids()

    def test_device_trace_events_unique_named_tids(self):
        _reset_lane_tids()
        try:
            lanes = {"/device:TPU:0/exec": [LaneEvent("dot.1", 1000, 2000)],
                     "/device:TPU:1/exec": [LaneEvent("dot.2", 1500, 2500)]}
            # the regression scenario: serving slots already claimed the
            # fixed-base tids a pre-registry exporter would have reused
            serving = [allocate_lane_tid(("serving", s)) for s in range(3)]
            events = device_trace_events(lanes)
            metas = [e for e in events if e.get("ph") == "M"
                     and e["name"] == "thread_name"]
            tids = [e["tid"] for e in metas]
            assert len(tids) == len(set(tids)) == 2
            assert not set(tids) & set(serving), (
                "device lanes reused serving-slot tids — a merged trace "
                "would mis-label one lane as the other")
            xs = [e for e in events if e.get("ph") == "X"]
            assert min(e["ts"] for e in xs) == 0.0   # capture-relative
            assert all(e["dur"] > 0 for e in xs)
        finally:
            _reset_lane_tids()

    def test_merged_trace_no_conflicting_thread_names(self, tmp_path):
        """Regression pin: one process exporting serving lanes AND
        xplane device lanes into the same trace must never map one
        (pid, tid) to two different thread names."""
        _reset_lane_tids()
        try:
            pid = os.getpid()
            events = device_trace_events(
                {"/device:TPU:0/exec": [LaneEvent("dot.1", 0, 1000)]})
            for slot in range(2):
                events.append({
                    "name": "thread_name", "ph": "M", "pid": pid,
                    "tid": allocate_lane_tid(("serving", slot)),
                    "args": {"name": f"serving slot {slot}"}})
            seen = {}
            for e in events:
                if e.get("ph") == "M" and e["name"] == "thread_name":
                    key = (e["pid"], e["tid"])
                    assert seen.setdefault(key, e["args"]["name"]) == \
                        e["args"]["name"], (
                        f"tid {key} claimed by both "
                        f"{seen[key]!r} and {e['args']['name']!r}")
        finally:
            _reset_lane_tids()


# ---------------------------------------------------------------------------
# extract_events: which lines of a plane are op lanes
# ---------------------------------------------------------------------------

def _plane(name, lines, stat_names=("hlo_op", "step")):
    """XPlane from {line_name: [(event_name, offset_ps, dur_ps, stats)]}."""
    from deepspeed_tpu.telemetry import xplane as xp
    plane = xp.XPlane(name=name,
                      stat_metadata={i + 1: n
                                     for i, n in enumerate(stat_names)})
    stat_id = {n: i for i, n in plane.stat_metadata.items()}
    ids = {}
    for line_name, events in lines.items():
        line = xp.XLine(name=line_name)
        for ev_name, off, dur, stats in events:
            mid = ids.setdefault(ev_name, len(ids) + 1)
            plane.event_metadata[mid] = {"name": ev_name}
            line.events.append(xp.XEvent(
                metadata_id=mid, offset_ps=off, duration_ps=dur,
                stats=[xp.XStat(metadata_id=stat_id[k], value=v)
                       for k, v in stats.items()]))
        plane.lines.append(line)
    return plane


class TestExtractEvents:
    def test_tpu_plane_only_the_xla_ops_line_is_a_lane(self):
        """A /device:TPU:n plane (v5e, jax 0.9) also carries Steps, XLA
        Modules and Async XLA Ops lines whose events span or overlap the
        ops; only 'XLA Ops' is the op lane, and its events — named by
        their full HLO text — join on the bare instruction name."""
        from deepspeed_tpu.telemetry import xplane as xp
        dot = ("%dot.1 = f32[8,32]{1,0:T(8,128)} dot(f32[8,32]{1,0} %p0, "
               "f32[8,32]{1,0} %p0), lhs_contracting_dims={1}")
        fus = ("%loop_add_fusion = f32[8,32]{1,0} fusion(f32[8,32]{1,0} "
               "%dot.1), kind=kLoop, calls=%fused_computation")
        ar = ("%all-reduce-start.2 = f32[8,32]{1,0} all-reduce-start("
              "f32[8,32]{1,0} %loop_add_fusion), replica_groups={}")
        device = _plane("/device:TPU:0", {
            "Steps": [("0", 0, 1000, {})],
            "XLA Modules": [("jit_step(123)", 0, 1000, {})],
            "XLA Ops": [(dot, 100, 300, {}), (fus, 400, 100, {}),
                        (ar, 500, 10, {})],
            "Async XLA Ops": [(ar, 500, 400, {"hlo_op": "all-reduce-done.2"})],
            "TC Overlay": [],
        })
        host = _plane("/host:CPU", {
            "python": [(sa.STEP_MARK, 0, 1000, {"step": 7})]})
        steps, lanes = sa.extract_events(xp.XSpace(planes=[device, host]))
        assert steps == [(7, 0, 1000)]
        assert list(lanes) == ["/device:TPU:0/XLA Ops"]
        assert [ev.name for ev in lanes["/device:TPU:0/XLA Ops"]] == [
            "dot.1", "loop_add_fusion", "all-reduce-start.2"]
        rep = analyze_events(steps, lanes, op_table=hlo_op_table(HLO_SNIPPET))
        assert rep["device_wall_s"] == pytest.approx(1000 * _PS_S)
        assert rep["categories_s"]["matmul_convolution"] == \
            pytest.approx(300 * _PS_S)
        assert rep["categories_s"]["collective"] == pytest.approx(10 * _PS_S)
        assert rep["ops_joined_to_hlo"] == 2      # dot.1, loop_add_fusion
        _sum_close(rep)

    def test_host_executor_lane_by_op_share(self):
        """jax 0.9 executor threads interleave every op with 'end:' and
        ThreadpoolListener events (ops are about a third of the line);
        the python thread, which runs a few programs inline, is not a
        lane — and only the ops themselves are taken."""
        from deepspeed_tpu.telemetry import xplane as xp
        executor = []
        for i in range(10):
            t = i * 100
            executor += [(f"dot.{i}", t, 60, {"hlo_op": f"dot.{i}"}),
                         (f"end: dot.{i}", t + 60, 1, {}),
                         ("ThreadpoolListener::Record", t + 61, 0, {})]
        python = [("$builtins isinstance", i, 1, {}) for i in range(200)]
        python.append(("copy", 300, 5, {"hlo_op": "copy"}))
        host = _plane("/host:CPU", {"tf_XLAPjRtCpuClient/1": executor,
                                    "python": python})
        _, lanes = sa.extract_events(xp.XSpace(planes=[host]))
        assert list(lanes) == ["/host:CPU/tf_XLAPjRtCpuClient/1"]
        assert [ev.name for ev in lanes["/host:CPU/tf_XLAPjRtCpuClient/1"]] \
            == [f"dot.{i}" for i in range(10)]


# ---------------------------------------------------------------------------
# summarize_capture on the committed fixture
# ---------------------------------------------------------------------------

class TestSummarizeCapture:
    def test_fixture_end_to_end(self, tmp_path):
        shutil.copy(FIXTURE, tmp_path / "cap.xplane.pb")
        rep = summarize_capture(str(tmp_path))
        assert rep is not None and "error" not in rep
        assert rep["captured_steps"] == 2
        assert rep["source"]["marked_steps"] == 2
        assert rep["lanes"], "no executor lane extracted from the fixture"
        assert rep["device_wall_s"] > 0
        assert rep["ops_total"] >= 1
        _sum_close(rep)

    def test_tpu_fixture_end_to_end(self, tmp_path):
        """A capture recorded on a TPU v5e (jax 0.9, libtpu 0.0.34; two
        steps of one small jitted program): the device plane's op line
        is the only lane, so busy time is booked once."""
        shutil.copy(TPU_FIXTURE, tmp_path / "cap.xplane.pb")
        rep = summarize_capture(str(tmp_path))
        assert rep is not None and "error" not in rep
        assert "/device:TPU:0" in rep["source"]["planes"]
        assert rep["captured_steps"] == 2
        assert [l["name"] for l in rep["lanes"]] == ["/device:TPU:0/XLA Ops"]
        assert rep["lanes"][0]["events"] == 6
        assert {o["name"] for o in rep["top_ops"]} == {
            "copy-start", "copy-done", "multiply_reduce_fusion"}
        assert 0 < rep["lanes"][0]["busy_s"] < rep["device_wall_s"]
        _sum_close(rep)

    def test_empty_dir_returns_none(self, tmp_path):
        assert summarize_capture(str(tmp_path)) is None

    def test_corrupt_capture_reports_error(self, tmp_path):
        (tmp_path / "bad.xplane.pb").write_bytes(b"\x0a\xff")
        rep = summarize_capture(str(tmp_path))
        assert rep is not None
        assert "byte offset" in rep["error"]
        assert rep["source"]["trace"].endswith("bad.xplane.pb")


# ---------------------------------------------------------------------------
# ledger capture post-processing (the escalation-evidence satellite)
# ---------------------------------------------------------------------------

def _capture_ledger(monkeypatch, tmp_path, **kw):
    """Enabled fake-clock ledger whose 'profiler' drops the committed
    fixture into the capture dir (the shape a real capture leaves)."""
    prof = tmp_path / "prof"
    monkeypatch.setattr(
        ledger_mod, "_start_trace",
        lambda d: shutil.copy(FIXTURE, os.path.join(d, "cap.xplane.pb")))
    monkeypatch.setattr(ledger_mod, "_stop_trace", lambda: None)
    kw.setdefault("profiler_capture", True)
    kw.setdefault("profiler_capture_steps", 2)
    kw.setdefault("warmup_windows", 0)
    kw.setdefault("log_fn", lambda *a, **k: None)
    kw.setdefault("snapshot_path", str(tmp_path / "GOODPUT.json"))
    kw.setdefault("profiler_dir", str(prof))
    led = ledger_mod.GoodputLedger(enabled=True, **kw)
    t = {"now": 0.0}
    led._clock = lambda: t["now"]
    led._t_start = 0.0
    led._last_snapshot_t = float("-inf")
    return led, t


class TestLedgerCapturePostprocess:
    def _escalate_and_finish(self, led, t):
        with led.attribute("input_wait"):
            t["now"] += 1.0
        led.tick(4)                   # escalates; capture starts
        led.note_step(5)
        led.note_step(6)              # 4 + capture_steps(2): capture stops

    def test_capture_summarized_into_escalation_entry(self, monkeypatch,
                                                      tmp_path):
        led, t = _capture_ledger(monkeypatch, tmp_path)
        self._escalate_and_finish(led, t)
        report_path = tmp_path / "prof" / "CAPTURE_ANATOMY.json"
        assert report_path.is_file(), "capture was not post-processed"
        with open(report_path) as f:
            rep = json.load(f, parse_constant=lambda tok: pytest.fail(
                f"CAPTURE_ANATOMY.json contains bare {tok!r}"))
        assert rep["schema"] == sa.ANATOMY_SCHEMA
        assert rep["captured_steps"] == 2
        anom = led.anomalies[-1]
        assert anom["capture_report"] == str(report_path)
        assert anom["capture_top_category"] in BUSY_CATEGORIES
        prof = led.report()["profiler"]
        assert prof["last_capture_report"] == str(report_path)
        assert prof["last_capture_top_category"] == \
            anom["capture_top_category"]
        # the escalation entry in the WRITTEN snapshot carries it too
        with open(tmp_path / "GOODPUT.json") as f:
            snap = json.load(f)
        assert any(a.get("capture_report") for a in snap["anomalies"])

    def test_postprocess_failure_never_raises(self, monkeypatch, tmp_path):
        led, t = _capture_ledger(monkeypatch, tmp_path)
        monkeypatch.setattr(
            ledger_mod, "_stop_trace",
            lambda: None)
        import deepspeed_tpu.telemetry.step_anatomy as sa_mod
        monkeypatch.setattr(sa_mod, "summarize_capture",
                            lambda *a, **k: 1 / 0)
        self._escalate_and_finish(led, t)     # must not raise
        assert led._last_capture_report is None

    def test_raw_trace_dirs_capped(self, monkeypatch, tmp_path):
        led, t = _capture_ledger(monkeypatch, tmp_path,
                                 keep_raw_traces=2)
        runs = tmp_path / "prof" / "plugins" / "profile"
        for i, name in enumerate(["r1", "r2", "r3", "r4"]):
            d = runs / name
            d.mkdir(parents=True)
            (d / "host.xplane.pb").write_bytes(b"")
            mt = 1_000_000 + i
            os.utime(d, (mt, mt))
        led._prune_raw_traces()
        assert sorted(p.name for p in runs.iterdir()) == ["r3", "r4"]

    def test_keep_raw_traces_from_config(self):
        cfg = deepspeed_tpu.DeepSpeedConfig({
            "train_batch_size": 8,
            "telemetry": {"enabled": True,
                          "anatomy": {"keep_raw_traces": 5}}})
        assert cfg.telemetry.anatomy_keep_raw_traces == 5
        led = ledger_mod.GoodputLedger.from_config(cfg.telemetry)
        assert led.keep_raw_traces == 5


# ---------------------------------------------------------------------------
# config surface
# ---------------------------------------------------------------------------

class TestAnatomyConfig:
    def test_defaults(self):
        cfg = deepspeed_tpu.DeepSpeedConfig({"train_batch_size": 8})
        t = cfg.telemetry
        assert t.anatomy_enabled is True
        assert t.anatomy_capture_steps == 3
        assert t.anatomy_keep_raw_traces == 2
        assert t.anatomy_report_file == ""

    def test_env_override_disables(self, monkeypatch):
        monkeypatch.setenv("DS_TELEMETRY_ANATOMY", "0")
        cfg = deepspeed_tpu.DeepSpeedConfig({
            "train_batch_size": 8,
            "telemetry": {"enabled": True, "anatomy": {"enabled": True}}})
        assert cfg.telemetry.anatomy_enabled is False

    def test_validation(self):
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError
        with pytest.raises(DeepSpeedConfigError, match="capture_steps"):
            deepspeed_tpu.DeepSpeedConfig({
                "train_batch_size": 8,
                "telemetry": {"anatomy": {"capture_steps": 0}}})
        with pytest.raises(DeepSpeedConfigError, match="keep_raw_traces"):
            deepspeed_tpu.DeepSpeedConfig({
                "train_batch_size": 8,
                "telemetry": {"anatomy": {"keep_raw_traces": -1}}})


def test_telemetry_init_keeps_anatomy_lazy():
    """Static guard: telemetry/__init__.py must not import xplane or
    step_anatomy at module level — engine init never pays for the
    parser (PEP 562 __getattr__ only)."""
    import deepspeed_tpu.telemetry as tel
    with open(tel.__file__) as f:
        tree = ast.parse(f.read())
    offenders = []
    for node in tree.body:                     # module level only
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        offenders += [m for m in mods
                      if m.endswith(".xplane") or m.endswith(".step_anatomy")]
    assert not offenders, (
        f"telemetry/__init__.py eagerly imports {offenders} — the xplane "
        f"parser must stay lazy")
    # ...and the lazy path still resolves
    assert tel.step_anatomy.ANATOMY_SCHEMA == sa.ANATOMY_SCHEMA


# ---------------------------------------------------------------------------
# demo + CLI
# ---------------------------------------------------------------------------

class TestDemoAndCli:
    def test_demo_report_schema_and_invariants(self):
        rep = sa._demo_report()
        assert rep["schema"] == sa.ANATOMY_SCHEMA
        assert rep["captured_steps"] == 3
        assert len(rep["lanes"]) == 2
        _sum_close(rep)
        for cat in CATEGORIES:
            assert rep["categories_s"][cat] > 0, (
                f"demo must exercise every category; {cat} is zero")
        assert any(r["flagged"] for r in rep["measured_vs_predicted"]), \
            "demo must show a flagged drift row"
        att = rep["module_attribution"]["matmul_convolution"]
        assert any("h_" in r["module"] for r in att)
        assert any(r["bucket"] for r in att)

    def test_cli_demo_writes_strict_json(self, tmp_path, capsys):
        out = tmp_path / "STEP_ANATOMY.json"
        assert sa.main(["--demo", "--out", str(out)]) == 0
        with open(out) as f:
            doc = json.load(f, parse_constant=lambda tok: pytest.fail(
                f"demo report contains bare {tok!r}"))
        assert doc["schema"] == sa.ANATOMY_SCHEMA
        rendered = capsys.readouterr().out
        assert "step anatomy: 3 step(s)" in rendered
        assert "matmul_convolution" in rendered

    def test_cli_render_report_json(self, tmp_path, capsys):
        out = tmp_path / "r.json"
        sa.main(["--demo", "--out", str(out)])
        capsys.readouterr()
        assert sa.main(["--render", str(out)]) == 0
        assert "device wall" in capsys.readouterr().out

    def test_cli_render_trace_dir_and_pb(self, tmp_path, capsys):
        shutil.copy(FIXTURE, tmp_path / "cap.xplane.pb")
        assert sa.main(["--render", str(tmp_path)]) == 0
        assert "2 step(s)" in capsys.readouterr().out
        assert sa.main(["--render", str(tmp_path / "cap.xplane.pb")]) == 0
        assert "2 step(s)" in capsys.readouterr().out

    def test_cli_render_empty_dir_fails(self, tmp_path, capsys):
        assert sa.main(["--render", str(tmp_path)]) == 1
        assert "no .xplane.pb" in capsys.readouterr().err

    def test_cli_no_args_prints_help(self, capsys):
        assert sa.main([]) == 2
        assert "usage" in capsys.readouterr().out.lower()


# ---------------------------------------------------------------------------
# e2e: engine.profile_step on a real CPU-jax engine
# ---------------------------------------------------------------------------

def _backend_compiles(engine):
    reg = engine.telemetry.registry
    return sum(m.value for ms in reg.collect().values() for m in ms
               if m.name == "xla_backend_compiles_total")


@pytest.fixture(scope="module")
def anatomy_engine(tmp_path_factory):
    # TelemetryManager installs its tracer globally (trace: True); restore
    # the prior global tracer on teardown so later modules see it disabled.
    from deepspeed_tpu.telemetry.tracer import get_tracer, set_tracer
    prev_tracer = get_tracer()
    tmp = tmp_path_factory.mktemp("anatomy")
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "telemetry": {"enabled": True, "trace": True, "jsonl": False,
                      "prometheus": False,
                      "output_path": str(tmp),
                      "cost_explorer": {"enabled": True},
                      "health": {"enabled": True}},
    }
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=32, nlayers=2), config=cfg,
        sample_batch=sample_batch(8, 32), seed=42)
    batch = sample_batch(8, 32)
    yield engine, batch, tmp
    engine.close()
    set_tracer(prev_tracer)


@pytest.mark.skipif(not ledger_mod.profiler_available(),
                    reason="jax.profiler programmatic capture unavailable")
class TestProfileStepE2E:
    def test_profile_step_writes_grounded_report(self, anatomy_engine):
        engine, batch, tmp = anatomy_engine
        engine.train_batch(batch=batch)          # prime the one compile
        before = _backend_compiles(engine)
        rep = engine.profile_step(3, batch=batch)
        after = _backend_compiles(engine)
        assert after == before, (
            f"profile_step added {after - before} XLA compiles — the "
            f"capture must reuse the primed step signature")
        assert rep.get("enabled") is True
        assert rep["schema"] == sa.ANATOMY_SCHEMA
        assert rep["captured_steps"] == 3
        assert rep["source"]["marked_steps"] == 3
        assert rep["device_wall_s"] > 0
        assert rep["lanes"], "no device/executor lanes captured"
        # the acceptance invariant: categories sum to device wall (<1%)
        total = sum(rep["categories_s"].values())
        assert abs(total - rep["device_wall_s"]) <= \
            0.01 * rep["device_wall_s"]
        # join grounded in the engine's OWN compiled HLO
        assert rep["ops_joined_to_hlo"] > 0
        assert rep["ops_total"] >= rep["ops_joined_to_hlo"]
        # a real model module must surface in the matmul attribution
        att = rep["module_attribution"]["matmul_convolution"]
        assert any(r["module"] for r in att), (
            f"no module attribution in {att}")
        # a measured-vs-predicted row for every roofline category
        rows = {r["category"] for r in rep["measured_vs_predicted"]}
        assert {"compute", "memory", "comm"} <= rows
        # report landed on disk, strict JSON, schema-pinned
        path = rep["report_path"]
        assert path == os.path.join(str(tmp), "STEP_ANATOMY.json")
        with open(path) as f:
            doc = json.load(f, parse_constant=lambda tok: pytest.fail(
                f"STEP_ANATOMY.json contains bare {tok!r}"))
        assert doc["schema"] == sa.ANATOMY_SCHEMA

    def test_merged_trace_lanes_exported(self, anatomy_engine):
        engine, batch, tmp = anatomy_engine
        rep = engine.profile_step(2, batch=batch)
        merged = rep.get("merged_trace")
        assert merged and os.path.isfile(merged)
        with open(merged) as f:
            doc = json.load(f)
        events = doc["traceEvents"]
        procs = {e["args"]["name"] for e in events
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert any("xplane" in p for p in procs), procs
        # no (pid, tid) may resolve to two different thread names
        seen = {}
        for e in events:
            if e.get("ph") == "M" and e["name"] == "thread_name":
                key = (e["pid"], e["tid"])
                assert seen.setdefault(key, e["args"]["name"]) == \
                    e["args"]["name"], f"conflicting names for tid {key}"

    def test_raw_trace_dirs_capped(self, anatomy_engine):
        engine, batch, tmp = anatomy_engine
        keep = engine.config.telemetry.anatomy_keep_raw_traces
        for _ in range(2):
            engine.profile_step(1, batch=batch)
        runs = [d for d in
                (tmp / "anatomy_profile" / "plugins" / "profile").iterdir()
                if d.is_dir()]
        assert len(runs) <= keep

    def test_disabled_is_inert(self, anatomy_engine, monkeypatch):
        engine, batch, _ = anatomy_engine
        monkeypatch.setattr(engine.config.telemetry, "anatomy_enabled",
                            False)
        rep = engine.profile_step(1, batch=batch)
        assert rep == {"enabled": False,
                       "reason": "telemetry.anatomy.enabled is false"}

    def test_profiler_unavailable_is_inert(self, anatomy_engine,
                                           monkeypatch):
        engine, batch, _ = anatomy_engine
        monkeypatch.setattr(ledger_mod, "profiler_available",
                            lambda: False)
        rep = engine.profile_step(1, batch=batch)
        assert rep["enabled"] is False
        assert "unavailable" in rep["reason"]
