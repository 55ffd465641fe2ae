"""The benchmark's copy of the trace reduction, on the recorded v5e capture
(two runs of one small jitted step, PR 21)."""

import pytest

from benchmark import harness, trace

CAPTURE = harness.ROOT / "tests" / "unit" / "data" / "tiny_tpu_capture.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(CAPTURE, chips=1)


def test_busy_and_idle_share(reduced):
    # two programs of 1.80 us, 1.0094 ms apart: the window spans both
    assert reduced.window_s == pytest.approx(1.0109e-3, rel=1e-3)
    assert reduced.busy_s == pytest.approx(2 * 1.5295e-6, rel=2e-2)
    assert 0.99 < reduced.idle_share < 1.0
    assert reduced.chips == 1


def test_longest_ops_and_gaps(reduced):
    top = reduced.breakdown()["device_ops"]
    assert top[0][0] == "multiply_reduce_fusion"
    assert top[0][1] == pytest.approx(2 * 1.51375e-6, rel=1e-2)
    assert {name for name, _ in top} == {"multiply_reduce_fusion",
                                         "copy-start", "copy-done"}
    gaps = reduced.breakdown()["idle_gaps"]
    assert gaps[0][1] == pytest.approx(1.0079e-3, rel=1e-2)
    assert gaps[0][0] == "outside-spans"    # the capture predates bench: spans


def test_programs(reduced):
    assert list(reduced.programs) == ["jit_step"]
    assert reduced.programs["jit_step"] == pytest.approx([1.802e-6, 1.799e-6],
                                                         rel=1e-2)


def test_names():
    attn = ('%attn.143 = (bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}) '
            'custom-call(bf16[128,1024,64] %bitcast.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace.instruction_name(attn) == "attn.143"
    assert trace.program_name("jit_fused_train_step(3001086554860140903)") \
        == "jit_fused_train_step"


def test_a_capture_without_a_device_plane_is_an_error(tmp_path):
    empty = tmp_path / "x.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(Exception):
        trace.reduce(empty, chips=1)
    with pytest.raises(FileNotFoundError):
        trace.find(tmp_path / "nothing")


# ------------------------------------------------- gaps named by host spans
MS = 1_000_000      # the capture's clock is in nanoseconds


def test_a_gap_is_named_by_the_innermost_span_over_its_longest_part():
    """One serving step: the device is done 2 ms before the wait returns,
    the host delivers for 0.5 ms, and the next dispatch starts the device
    3 ms in. The 5.8 ms gap lies under four spans; its midpoint (2.9 ms in)
    falls in the dispatch, and so does its longest part."""
    spans = [("step", 0, 21 * MS),
             ("serving_step", int(0.1 * MS), 20 * MS),
             ("serving_decode_wait", 1 * MS, 8 * MS),
             ("serving_deliver", 8 * MS, int(8.5 * MS)),
             ("serving_publish", int(8.5 * MS), int(8.8 * MS)),
             ("serving_decode", int(8.8 * MS), 19 * MS),
             ("serving_decode_dispatch", 9 * MS, 13 * MS)]
    line = trace.innermost(spans)
    assert [name for _, _, name in line] == [
        "step", "serving_step", "serving_decode_wait", "serving_deliver",
        "serving_publish", "serving_decode", "serving_decode_dispatch",
        "serving_decode", "serving_step", "step"]
    assert trace.name_gap(6 * MS, int(11.8 * MS), line) \
        == "serving_decode_dispatch"
    # the sync's tail alone; a gap whose midpoint lies in a short span
    # between two long parts of another
    assert trace.name_gap(6 * MS, int(8.2 * MS), line) \
        == "serving_decode_wait"
    assert trace.name_gap(int(8.7 * MS), int(8.9 * MS), line) in (
        "serving_publish", "serving_decode")
    assert trace.name_gap(6 * MS, int(8.6 * MS), line) \
        == "serving_decode_wait"              # midpoint 7.3 ms, the wait too
    assert trace.name_gap(int(7.9 * MS), int(9.2 * MS), line) \
        == "serving_deliver"                  # 0.5 of 1.3 ms; midpoint: publish
    # no span at all over most of it
    assert trace.name_gap(20 * MS, 40 * MS, line) == "outside-spans"
    assert trace.name_gap(0, 1, []) == "outside-spans"


def test_the_programs_spans_are_read_beside_the_harnesss_own():
    import types

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)
    host = types.SimpleNamespace(name="/host:CPU", lines=[
        types.SimpleNamespace(events=[
            ev("bench:window", 0, 100), ev("bench:step", 10, 50),
            ev("serving_step", 11, 48), ev("serving_decode_wait", 20, 30),
            ev("train_dispatch", 70, 5), ev("fused_step", 69, 8),
            ev("PjitFunction(decode)", 12, 3), ev("$threading.py:1 run", 0, 9)])])
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(events=[ev("serving_not_a_host_span", 0, 1)])])
    spans = trace._host_spans(types.SimpleNamespace(planes=[host, device]),
                              "bench:")
    assert sorted(spans) == sorted([
        ("window", 0, 100), ("step", 10, 60), ("serving_step", 11, 59),
        ("serving_decode_wait", 20, 50), ("train_dispatch", 70, 75),
        ("fused_step", 69, 77)])


def _capture(monkeypatch, device_ops, host):
    """A capture made by hand, read through ``trace.reduce`` itself."""
    import types
    import jax.profiler

    def ev(name, start, dur):
        return types.SimpleNamespace(name=name, start_ns=start,
                                     duration_ns=dur)
    planes = [
        types.SimpleNamespace(name="/host:CPU", lines=[types.SimpleNamespace(
            name="main", events=[ev(*e) for e in host])]),
        types.SimpleNamespace(name="/device:TPU:0", lines=[
            types.SimpleNamespace(name="XLA Ops",
                                  events=[ev(*e) for e in device_ops]),
            types.SimpleNamespace(name="XLA Modules", events=[
                ev("jit_decode(1)", s, d) for _, s, d in device_ops])])]
    fake = types.SimpleNamespace(
        from_file=lambda path: types.SimpleNamespace(planes=planes))
    monkeypatch.setattr(jax.profiler, "ProfileData", fake)
    return trace.reduce("hand-made", chips=1)


def test_a_device_trace_that_ends_early_cuts_the_window_there(monkeypatch):
    """The device records nothing once its trace buffer is full: steps 10 ms
    apart, 6 ms busy each, through a 1 s window, of which the device trace
    holds the first 0.6 s (and the settling steps before the window)."""
    steps = range(-20, 100)
    host = [("bench:window", 0, 1000 * MS)] + [
        e for k in steps for e in (
            ("bench:step", k * 10 * MS, 10 * MS),
            ("serving_decode_dispatch", k * 10 * MS, 3 * MS),
            ("serving_decode_wait", k * 10 * MS + 3 * MS, 7 * MS))]
    ops = [("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", k * 10 * MS + 2 * MS,
            6 * MS) for k in steps if k < 60]
    r = _capture(monkeypatch, ops, host)
    assert r.window_s == pytest.approx(0.598) and r.cut_s == pytest.approx(0.402)
    assert r.busy_s == pytest.approx(60 * 6e-3)
    assert r.idle_share == pytest.approx(1 - 0.36 / 0.598)
    gaps = r.breakdown()["idle_gaps"]
    assert max(s for _, s in gaps) == pytest.approx(4e-3)     # no 0.4 s "gap"
    # each gap: 2 ms after the last operation under the wait, 2 ms under
    # the next dispatch, whose start comes first in time: either names it
    assert {name for name, _ in gaps} <= {"serving_decode_wait",
                                          "serving_decode_dispatch"}
    assert len(r.programs["jit_decode"]) == 60
    # the whole window traced: nothing is cut, the window is the host's
    whole = _capture(monkeypatch, ops + [
        ("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p)", k * 10 * MS + 2 * MS,
         6 * MS) for k in range(60, 100)], host)
    assert whole.cut_s == 0 and whole.window_s == pytest.approx(1.0)
    assert whole.busy_s == pytest.approx(0.6)
    # a trace that ended before the window opened is no reading at all
    with pytest.raises(ValueError, match="ended before the window opened"):
        _capture(monkeypatch, [op for op in ops if op[1] < 0], host)
