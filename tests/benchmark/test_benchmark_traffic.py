"""``offline-backlog``: the seed decides token ids (and weights) and nothing
else. Lengths, their order, the end of the fill-up and the schedule of
every step are the same for every seed."""

import json

import numpy as np
import pytest

import benchmark_tiny
from benchmark import harness, traffic
from benchmark.run import measure


@pytest.fixture(scope="module")
def backlog():
    return json.loads(
        (harness.HERE / "traffic" / "offline-backlog.json").read_text())


def test_lengths_follow_the_file_and_stay_inside_the_positions(backlog):
    shapes = traffic.request_shapes(backlog)
    prompts, outputs = (np.array(x) for x in zip(*shapes))
    assert len(shapes) == 512
    assert prompts.min() == 32 and 630 <= prompts.max() <= 640
    assert outputs.min() == 32 and 250 <= outputs.max() <= 256
    assert abs(np.median(prompts) - 143) <= 1
    assert abs(np.median(outputs) - 90) <= 1
    assert (prompts + outputs).max() <= 896 < 1024
    # the list is a permutation of the quantiles: the seed plays no part
    assert sorted(prompts) == sorted(
        traffic.quantile_lengths(dict(backlog["prompt_len"], stride=1), 512))


def test_prompts_and_outputs_are_uncorrelated_and_every_batch_spans_both(
        backlog):
    prompts, outputs = (np.array(x, float)
                        for x in zip(*traffic.request_shapes(backlog)))
    assert abs(np.corrcoef(np.log(prompts), np.log(outputs))[0, 1]) < 0.1
    for start in range(0, 512, 8):
        idx = np.arange(start, start + 64) % 512
        for lengths, lo, hi in ((prompts, 32, 640), (outputs, 32, 256)):
            window = lengths[idx]
            assert window.min() <= lo * 1.2 and window.max() >= hi / 1.2


def test_a_stride_that_is_no_permutation_is_refused(backlog):
    with pytest.raises(ValueError, match="coprime"):
        traffic.quantile_lengths(dict(backlog["prompt_len"], stride=64), 512)


def test_the_seed_changes_token_ids_only():
    a = traffic.prompt_ids(3_000_000_001, 7, 100, 50257)
    assert np.array_equal(a, traffic.prompt_ids(3_000_000_001, 7, 100, 50257))
    assert not np.array_equal(a, traffic.prompt_ids(3_000_000_002, 7, 100, 50257))
    assert not np.array_equal(a, traffic.prompt_ids(3_000_000_001, 8, 100, 50257))
    assert a.min() >= 0 and a.max() < 50257
    rows = traffic.train_batch_ids(2 ** 31 + 5, 0, 8, 64, 512)
    assert len({r.tobytes() for r in rows}) == 8        # rows all differ
    assert not np.array_equal(rows, traffic.train_batch_ids(2 ** 31 + 5, 1, 8, 64, 512))


@pytest.fixture(scope="module")
def two_seeds():
    cell = benchmark_tiny.cell("tiny-backlog")
    return [measure(cell, seed, 1.5, 0) for seed in (11, 3_000_000_019)]


def test_two_seeds_drive_the_identical_schedule(two_seeds):
    (_, _, a), (_, _, b) = two_seeds
    ra, rb = a["records"], b["records"]
    assert ra["fill_up"] == rb["fill_up"] and len(ra["fill_up"]) > 3
    sa = [s[2:] for s in ra["steps"]]
    sb = [s[2:] for s in rb["steps"]]
    n = min(len(sa), len(sb))        # the window closes on the clock
    assert n > 20 and sa[:n] == sb[:n]
    assert ra["at_open"] == rb["at_open"]
    assert any(chunks for chunks, _ in sa[:n]) and any(d for _, d in sa[:n])
    # ... on different work: the tokens differ
    assert [t.tolist() for _, t in a["evidence"]] != \
        [t.tolist() for _, t in b["evidence"]]


def test_the_window_counts_unfinished_requests_and_the_whole_window(two_seeds):
    line, checks, out = two_seeds[0]
    rec = out["records"]
    gained = {rid: n - rec["at_open"].get(rid, 0)
              for rid, n in rec["at_close"].items()}
    assert rec["delivered"] == sum(gained.values()) > 0
    unfinished = [rid for rid, n in rec["at_close"].items()
                  if 0 < n < rec["shape_of"][rid][1]]
    assert unfinished and sum(gained[r] for r in unfinished) > 0
    assert rec["delivered"] == sum(d for *_, d in rec["steps"])
    assert rec["window_s"] >= 1.5
    rate = json.loads(line)["metrics"]["serve_tokens_per_s"]["value"]
    assert rate == pytest.approx(rec["delivered"] / rec["window_s"])
    assert rec["window_s"] >= rec["steps"][-1][1] - rec["steps"][0][0]
    assert rec["compiles_in_window"] == 0
    assert json.loads(line)["correct"] is True
