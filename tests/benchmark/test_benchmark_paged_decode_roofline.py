"""``offline_paged_decode_roofline`` on the tiny mix (``tiny.json``: 2
layers x 64, so a cached token of one layer is 256 B and 256 operations):
a hand-made trace with two ``paged_decode.N`` operations and
``serving_decode`` spans whose ``blocks_needed`` are known. A CPU run
yields counts, never a speed: the times here are written, not taken."""

import types

import pytest

from benchmark import harness, program_spans

import benchmark_tiny

W0, W1 = 100.0, 110.0           # the window, seconds on perf_counter
NAME = "offline_paged_decode_roofline"
SPANS = [{"batch": 4, "blocks_needed": 1000, "blocks_visited": 1000},
         {"batch": 4, "blocks_needed": 3000, "blocks_visited": 3000},
         {"batch": 3}]
OPS = {"paged_decode.1": 1.0e-4, "paged_decode.2": 1.5e-4, "fusion.7": 9.0}


def _ctx(monkeypatch, decode_spans, ops):
    events = [{"name": "serving_decode", "ph": "X",
               "ts": int((W0 + 1 + i) * 1e6), "dur": 1000, "pid": 1,
               "tid": 1, "args": args}
              for i, args in enumerate(decode_spans)]
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    return {"spans": [("window", W0, W1)],
            "cell": benchmark_tiny.cell("tiny-backlog"),
            "trace": types.SimpleNamespace(ops=ops),
            "device_kind": "TPU v5 lite"}


def test_the_share_is_the_needed_bytes_over_the_kernels_time(monkeypatch):
    got = harness.load_reader(NAME)(_ctx(monkeypatch, SPANS, OPS))
    # 4,000 blocks x 16 tokens x 2 layers x (K row + V row) x 64 x 2 B
    need_bytes = 4000 * 16 * 2 * 2 * 64 * 2
    assert need_bytes == 32_768_000
    assert got == pytest.approx(100 * (need_bytes / 819e9) / 2.5e-4)
    assert got == pytest.approx(16.0039, abs=1e-4) and 0 < got < 100


def test_the_cost_is_one_operation_a_byte_and_memory_binds():
    from benchmark import flops
    reader = harness.load_named("metrics", NAME)
    config = benchmark_tiny.TINY["config"]
    cost = reader.paged_decode_cost(config, 10, 16)
    assert cost == {"flops": 10 * 16 * 2 * 4 * 64.0,
                    "bytes": 10 * 16 * 2 * 2 * 64 * 2.0}
    assert cost["flops"] == cost["bytes"]
    assert flops.roofline_seconds(
        cost, flops.peaks("TPU v5 lite"))["bound"] == "memory"


@pytest.mark.parametrize("why,spans,ops", [
    ("no-such-name", SPANS, {"fusion.7": 9.0, "paged_decoder.1": 1.0}),
    ("no-blocks", [{"batch": 3}], OPS),
    ("no-spans", [], OPS)])
def test_nothing_to_read(monkeypatch, why, spans, ops):
    assert harness.load_reader(NAME)(_ctx(monkeypatch, spans, ops)) is None


def test_the_manifest_names_the_two_gpt2_serve_cells():
    (entry,) = [m for m in harness.load_manifest()["per_layer"]
                if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "serve programs",
        "moves": "serve_tokens_per_s",
        "workloads": ["gpt2-medium.serve.offline", "gpt2-xl.serve.offline"]}
