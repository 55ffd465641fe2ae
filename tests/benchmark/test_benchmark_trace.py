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


def test_programs_and_custom_calls(reduced):
    assert list(reduced.programs) == ["jit_step"]
    assert reduced.programs["jit_step"] == pytest.approx([1.802e-6, 1.799e-6],
                                                         rel=1e-2)
    assert reduced.custom_call_s == 0.0     # no Pallas kernel in this capture


def test_names():
    attn = ('%attn.143 = (bf16[128,1024,64]{2,1,0}, bf16[128,1024,64]{2,1,0}) '
            'custom-call(bf16[128,1024,64] %bitcast.1), '
            'custom_call_target="tpu_custom_call"')
    assert trace.instruction_name(attn) == "attn.143"
    assert trace.is_custom_call(attn)
    assert not trace.is_custom_call(
        '%custom-call.299 = f32[1024,1024] custom-call(f32[256,1024] %s), '
        'custom_call_target="ConcatBitcast"')
    assert not trace.is_custom_call(
        "%copy.2447 = f32[1024,1024] copy(f32[1024,1024] %custom-call.299)")
    assert trace.program_name("jit_fused_train_step(3001086554860140903)") \
        == "jit_fused_train_step"


def test_a_capture_without_a_device_plane_is_an_error(tmp_path):
    empty = tmp_path / "x.xplane.pb"
    empty.write_bytes(b"")
    with pytest.raises(Exception):
        trace.reduce(empty, chips=1)
    with pytest.raises(FileNotFoundError):
        trace.find(tmp_path / "nothing")
