"""The reduction of the program's own spans (``benchmark/program_spans.py``)
on hand-written events, and each reader PR 26 added on a hand-made ``ctx``:
what it reads where the spans are there, and nothing where the tracer is
empty (which is what the parent commit's tracer is in a benchmark run)."""

import types

import pytest

from benchmark import harness, program_spans

import benchmark_tiny

W0, W1 = 100.0, 110.0           # the window, seconds on perf_counter
SPAN_READERS = ["offline_turnaround_ms_p50", "offline_step_host_ms_p50",
                "offline_decode_dispatch_ms_p50", "offline_paged_block_use",
                "train_host_ms_p50"]
FLASH_READERS = ["train_flash_fwd_roofline", "train_flash_bwd_roofline"]


def ev(name, start_ms, dur_ms, tid=1, **args):
    """A complete Chrome-trace event ``start_ms`` after the window opens."""
    e = {"name": name, "ph": "X", "ts": int(W0 * 1e6 + start_ms * 1e3),
         "dur": int(dur_ms * 1e3), "pid": 1, "tid": tid}
    if args:
        e["args"] = args
    return e


@pytest.fixture
def program(monkeypatch):
    """Sets what the program's tracer holds."""
    def hold(events):
        monkeypatch.setattr(program_spans, "program_events", lambda: events)
    hold([])
    return hold


def ctx(**more):
    return {"spans": [("setup.engine", 1.0, 2.0), ("window", W0, W1)],
            **more}


# --------------------------------------------------------------- reduction
def test_nested_spans_self_time_is_duration_less_children():
    events = [ev("inner", 2, 3), ev("leaf", 3, 1), ev("outer", 1, 10)]
    (outer,) = program_spans.nest(events, W0, W1)
    (inner,) = outer.children
    assert [c.name for c in inner.children] == ["leaf"]
    assert outer.seconds == pytest.approx(10e-3)
    assert outer.self_seconds == pytest.approx(7e-3)
    assert inner.self_seconds == pytest.approx(2e-3)
    assert [s.name for s in outer.find("leaf", "inner")] == ["inner", "leaf"]


def test_adjacent_spans_are_siblings_and_threads_do_not_mix():
    events = [ev("a", 1, 2), ev("b", 3, 2), ev("parent", 1, 4),
              ev("elsewhere", 2, 1, tid=2)]
    roots = program_spans.nest(events, W0, W1)
    assert [r.name for r in roots] == ["parent", "elsewhere"]
    parent = roots[0]
    assert [c.name for c in parent.children] == ["a", "b"]
    assert parent.self_seconds == pytest.approx(0.0, abs=1e-9)


def test_a_span_cut_by_the_windows_edge_is_clipped_with_its_children():
    events = [ev("child", 9_998, 5), ev("late", 9_997, 10),
              ev("early", -1, 5), ev("after", 10_001, 1)]
    (late,) = program_spans.nest(events, W0, W1)   # early began outside
    assert late.end == pytest.approx(W1)
    assert late.seconds == pytest.approx(3e-3)
    assert late.children[0].seconds == pytest.approx(2e-3)
    assert late.self_seconds == pytest.approx(1e-3)


def test_spans_that_begin_in_one_microsecond_nest_by_length_then_order():
    inner, outer = ev("x", 1, 2), ev("x", 1, 2)     # recorded inner first
    longer = ev("longest", 1, 3)
    (root,) = program_spans.nest([inner, outer, longer], W0, W1)
    assert root.name == "longest"
    assert len(root.children) == 1 and len(root.children[0].children) == 1


def test_no_window_row_reads_nothing(program):
    program([ev("serving_step", 1, 1)])
    assert program_spans.named({"spans": []}, "serving_step") == []


# ----------------------------------------------------------------- readers
def _serve_steps():
    """Three steps 100 ms apart, each waiting on the device from 20 to 60
    ms into the step. Step 1 opens with a prefill chunk 3 ms in; step 2's
    decode dispatch comes 5 ms in; dispatches take 3, 4 and 5 ms."""
    out = []
    for k, (decode_at, dispatch_at) in enumerate([(7, 10), (7, 10), (3, 5)]):
        t = 100.0 * k
        if k == 1:
            out.append(ev("serving_prefill", t + 3, 2, req=9, start=0,
                          tokens=6))
        out += [ev("serving_schedule", t, 0.5),
                ev("serving_decode_inputs", t + decode_at + 0.5, 1),
                ev("serving_decode_dispatch", t + dispatch_at, 3 + k),
                ev("serving_decode_wait", t + 20, 40),
                ev("serving_deliver", t + 60, 0.5),
                ev("serving_decode", t + decode_at, 54, batch=3,
                   blocks_needed=5 + k, blocks_visited=12),
                ev("serving_publish", t + 61, 0.25),
                ev("serving_step", t, 62)]
    return out


def test_turnaround_is_wait_end_to_the_next_first_dispatch(program):
    program(_serve_steps())
    read = harness.load_reader("offline_turnaround_ms_p50")
    # step 0 -> 1: 60 -> 103; step 1 -> 2: 160 -> 205
    assert read(ctx()) == pytest.approx((43.0 + 45.0) / 2)


def test_step_host_is_the_step_less_its_wait(program):
    program(_serve_steps())
    read = harness.load_reader("offline_step_host_ms_p50")
    assert read(ctx()) == pytest.approx(62.0 - 40.0)


def test_decode_dispatch_is_the_median_dispatch_span(program):
    program(_serve_steps())
    read = harness.load_reader("offline_decode_dispatch_ms_p50")
    assert read(ctx()) == pytest.approx(4.0)


def test_paged_block_use_sums_the_decode_spans_counts(program):
    program(_serve_steps())
    read = harness.load_reader("offline_paged_block_use")
    assert read(ctx()) == pytest.approx(100.0 * (5 + 6 + 7) / 36)


def test_train_host_is_the_median_train_batch_span(program):
    program([ev("train_batch", t, d) for t, d in
             [(0, 3.0), (200, 3.5), (400, 9.0)]]
            + [ev("train_dispatch", 1, 1), ev("train_batch", -5, 2)])
    read = harness.load_reader("train_host_ms_p50")
    assert read(ctx()) == pytest.approx(3.5)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_span_reader_reads_nothing_from_an_empty_tracer(program, name):
    assert harness.load_reader(name)(ctx()) is None


def _train_ctx(ops):
    cell = benchmark_tiny.cell("tiny-train")
    trace = types.SimpleNamespace(ops=ops)
    return ctx(cell=cell, trace=trace, device_kind="TPU v5 lite",
               records={"global_batch": 8, "seq_len": 64,
                        "steps": [(0.0, 1.0)] * 5})


def test_flash_forward_and_backward_share_the_calls_least_time():
    from benchmark import flops
    made = _train_ctx({"flash_fwd.1": 2e-4, "flash_fwd.7": 2e-4,
                       "flash_dq.3": 5e-4, "flash_dkv.2": 7e-4,
                       "fusion.9": 1.0})
    fwd, bwd = (harness.load_reader(n)(made) for n in FLASH_READERS)
    # one bound (memory, at the test size) sets both, so the parts' least
    # times add up to the whole call's: 4 of 12 passes in 4e-4 s, 8 in 12e-4
    assert fwd / bwd == pytest.approx((4 / 4e-4) / (8 / 12e-4))
    cfg = made["cell"].config
    call = flops.flash_causal_call(8, cfg["n_head"], 64,
                                   cfg["n_embd"] // cfg["n_head"])
    least = flops.roofline_seconds(call, flops.peaks("TPU v5 lite"))
    assert least["seconds"] * cfg["n_layer"] * 5 == pytest.approx(
        (fwd * 4e-4 + bwd * 12e-4) / 100.0)


@pytest.mark.parametrize("name", FLASH_READERS)
def test_a_flash_reader_reads_nothing_without_the_kernels_names(name):
    made = _train_ctx({"attn.143": 3e-4, "fusion.9": 1.0})
    assert harness.load_reader(name)(made) is None
