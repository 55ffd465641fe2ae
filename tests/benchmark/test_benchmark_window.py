"""What a serve window counts, and the sample of it that ``correct``
compares: a traced run reaches the steady state before the profiler starts
and counts none of those steps; the sample holds a request of every slot,
from the window's open to its close, so one slot's wrong token is seen."""

import contextlib
import dataclasses
import json
import time

import numpy as np
import pytest

import benchmark_tiny
from benchmark import harness
from benchmark.kinds import serve_closed
from benchmark.run import measure

SEED = 3_000_000_011


class TracerLessTheProfiler:
    """``harness.Tracer`` switched on, less the profiler (there is no
    device to trace on the CPU): calls ``settle`` where the real one does,
    between starting the profiler and opening the window."""

    on = True

    def __init__(self, spans):
        self.spans, self.opened_at = spans, None

    @contextlib.contextmanager
    def window(self, devices, settle=None):
        self.opened_at = time.perf_counter()
        settle()
        with self.spans("window"):
            yield


@pytest.mark.parametrize("traced", [False, True])
def test_the_window_counts_its_own_steps_and_a_traced_one_opens_in_steady_state(
        traced):
    cell = benchmark_tiny.cell("tiny-backlog")
    spans = harness.Spans()
    tracer = TracerLessTheProfiler(spans) if traced \
        else harness.Tracer(False, spans)
    out = serve_closed.run(cell, SEED, 1.0, tracer, spans,
                           harness.CompileCounter())
    rec = out["records"]
    stepped = [row for row in spans.rows if row[0] == "step"]
    steady = [row for row in spans.rows if row[0] == "setup.steady"]
    first = range(rec["max_batch"])
    finished_at_open = [rec["at_open"][rid] == rec["shape_of"][rid][1]
                        for rid in first]
    if traced:
        # each of the fill-up's requests has finished before the profiler
        # starts; then the two settling steps; none of them is counted
        (steady,) = steady
        assert all(finished_at_open) and steady[2] <= tracer.opened_at
        before = [row for row in stepped if row[2] <= rec["steps"][0][0]]
        in_steady = [row for row in before if steady[1] <= row[1]
                     and row[2] <= steady[2]]
        assert len(in_steady) > 10
        assert len(before) == len(rec["fill_up"]) + len(in_steady) + 2
    else:
        assert not steady and not any(finished_at_open)
        assert len(stepped) == len(rec["fill_up"]) + len(rec["steps"])
    assert rec["delivered"] == sum(d for *_, d in rec["steps"]) > 0
    assert rec["delivered"] == sum(rec["at_close"].values()) \
        - sum(rec["at_open"].values())
    assert out["failed"] == 0 and out["attempted"] > 0
    # every request of the tiny mix is compared: all slots, all steps
    assert rec["sample"]["slots_share"] == rec["sample"]["steps_share"] == 1.0
    assert rec["sample"]["tokens"] >= rec["delivered"]


def test_a_serve_closed_mix_says_how_many_requests_a_slot_are_compared():
    cell = benchmark_tiny.cell("tiny-backlog")
    traffic = {k: v for k, v in cell.traffic.items()
               if k != "check_requests_per_slot"}
    spans = harness.Spans()
    with pytest.raises(KeyError, match="check_requests_per_slot"):
        serve_closed.run(dataclasses.replace(cell, traffic=traffic), SEED,
                         1.0, harness.Tracer(False, spans), spans, None)


# ------------------------------------------------- the sample that is compared
def _window(slots, a_slot):
    """``slots`` slots that each served ``a_slot`` requests in turn (ids in
    the order served), one of them the longest of all."""
    served, slot_of = {}, {}
    for rid in range(slots * a_slot):
        served[rid] = (np.zeros(8 + rid % 5, np.int32),
                       np.zeros(3 + rid % 7, np.int32))
        slot_of[rid] = rid % slots
    served[slots + 1] = (np.zeros(64, np.int32), np.zeros(48, np.int32))
    return served, slot_of


@pytest.mark.parametrize("per_slot", [1, 2])
def test_the_sample_holds_every_slot_and_both_ends_of_the_window(per_slot):
    slots, a_slot = 16, 10
    served, slot_of = _window(slots, a_slot)
    samples = set()
    for seed in range(SEED, SEED + 20):
        a = serve_closed.check_sample(seed, served, slot_of, per_slot)
        assert a == serve_closed.check_sample(
            seed, dict(reversed(served.items())), slot_of, per_slot)
        assert a == sorted(set(a)) and slots + 1 in a       # the longest
        assert per_slot * slots <= len(a) <= per_slot * slots + 1
        by_slot = {}
        for rid in a:
            by_slot.setdefault(slot_of[rid], []).append(rid // slots)
        assert sorted(by_slot) == list(range(slots))
        turns = sorted(t for ts in by_slot.values() for t in ts)
        # a request held at the open, one held at the close, and between
        # them no stretch of the window without one
        assert turns[0] == 0 and turns[-1] == a_slot - 1
        assert set(turns) == set(range(a_slot))
        if per_slot == 2:       # one of a slot's early half, one of its late
            assert all(min(ts) < a_slot / 2 <= max(ts)
                       for ts in by_slot.values())
        samples.add(tuple(a))
    assert len(samples) == 20           # the seed draws it
    # a slot that served no more than asked for gives all it served
    few, few_slots = _window(4, 2)
    assert serve_closed.check_sample(SEED, few, few_slots, 2) == list(range(8))
    assert serve_closed.check_sample(SEED, few, few_slots, 5) == list(range(8))


@pytest.fixture(scope="module")
def one_a_slot():
    cell = benchmark_tiny.cell("tiny-backlog")
    return dataclasses.replace(
        cell, traffic={**cell.traffic, "check_requests_per_slot": 1})


def test_one_request_a_slot_covers_the_slots_and_the_steps(one_a_slot):
    line, checks, out = measure(one_a_slot, SEED, 2.0, 0)
    covered = out["records"]["sample"]
    slots = out["records"]["max_batch"]
    assert json.loads(line)["correct"] is True, checks
    assert slots <= covered["requests"] <= slots + 1
    assert covered["slots_share"] == 1.0 and 0.1 < covered["steps_share"] < 1
    assert covered["tokens"] < 0.5 * out["records"]["delivered"]


@pytest.mark.parametrize("slot", [0, 3])
def test_a_wrong_token_in_one_slot_alone_is_not_correct(monkeypatch,
                                                        one_a_slot, slot):
    from deepspeed_tpu.serving.runner import PagedGPT2Runner
    real = PagedGPT2Runner.decode_step

    def altered(self, *args, **kwargs):
        pools, tokens = real(self, *args, **kwargs)
        tokens = tokens.at[:, slot].set(       # [steps a dispatch, slots]
            (tokens[:, slot] + 1) % self.cfg.vocab_size)
        return pools, tokens

    monkeypatch.setattr(PagedGPT2Runner, "decode_step", altered)
    line, checks, out = measure(one_a_slot, SEED, 2.0, 0)
    assert json.loads(line)["correct"] is False
    assert {n for n, _, _, ok in checks if not ok} \
        >= {"top_gap_max", "top_gap_mean"}


def test_rows_compared_in_groups_of_like_width_read_the_same(monkeypatch):
    cell = benchmark_tiny.cell("tiny-backlog")       # every request compared
    _, _, out = measure(cell, SEED, 1.0, 0)
    # sound tokens may all be the reference's best: alter every tenth, so
    # that there are gaps to read
    sample = [(p, np.where(np.arange(len(t)) % 10 == 9, (t + 1) % 8192, t))
              for p, t in out["evidence"]]
    assert len({-(-(len(p) + len(t)) // 32) for p, t in sample}) >= 3
    wide = serve_closed.numbers(cell, SEED, sample)
    monkeypatch.setattr(serve_closed, "ROW_WIDTH", 32)
    narrow = serve_closed.numbers(cell, SEED, sample)
    assert narrow["top_gap_max"] > 1 and narrow["top_gap_mean"] > 0.1
    assert narrow == pytest.approx(wide, rel=1e-4)


# ------------------------------------------- the pool that the traffic fills
def test_the_stubbed_schedule_holds_the_blocks_that_the_real_one_holds():
    """``benchmark/pool_demand.py`` sizes a cell's ``num_blocks`` from the
    scheduler alone: with the device programs stubbed it allocates what it
    allocates with them."""
    from benchmark import pool_demand
    cell = benchmark_tiny.cell("tiny-backlog")
    serving = cell.config["deployment"]["serving"]
    stubbed = pool_demand.demand(cell, serving, 150)
    real = pool_demand.demand(cell, serving, 150, stub=False)
    assert stubbed == real
    assert 4 * 4 < stubbed["blocks_peak"] < stubbed["default_num_blocks"] - 1
    assert stubbed["preemptions"] == 0
