"""``correct`` comes out true for sound runs and false for the control and
for every fault a cell can have: the harness is driven as a chip run drives
it (minus the look for a chip), at the test size, with the timed path broken
underneath. The limits used here were set on the CPU at this size
(``tiny.json``, ``limits``); the cells' own come from chip runs."""

import json

import numpy as np
import pytest

import benchmark_tiny
from benchmark import harness
from benchmark.kinds import serve_closed, train
from benchmark.run import measure

SEED = 3_000_000_007        # more than 32 signed bits hold


def _failed(checks):
    return {name for name, _, _, ok in checks if not ok}


# ------------------------------------------------------------------ training
@pytest.fixture(scope="module")
def train_cell():
    return benchmark_tiny.cell("tiny-train")


@pytest.fixture(scope="module")
def sound_train(train_cell):
    return measure(train_cell, SEED, 0.3, 0)


def test_a_sound_training_run_is_correct(sound_train):
    line, checks, out = sound_train
    result = json.loads(line)
    assert result["correct"] is True and result["failed"] == 0
    assert list(result)[-1] == "checks"         # the numbers come last
    assert set(result["checks"]) == {
        "loss_gap_step1", "loss_gap_step2", "loss_gap_step3",
        "grad_norm_gap", "grad_apart_median", "change_norm_gap"}
    assert all(c["value"] <= c["limit"] for c in result["checks"].values())
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["records"]["compiles_in_window"] == 0
    assert result["attempted"] == len(out["records"]["steps"]) >= 3


def test_the_key_bias_is_left_out_of_the_change_by_the_rule(sound_train,
                                                            train_cell):
    import jax.numpy as jnp
    from benchmark.reference import gpt2 as ref
    want = sound_train[2]["reference"]
    names = ref.leaf_names(ref.make_weights(
        ref.seed_words(SEED), ref.sizes(train_cell.config), jnp.float32))
    g = np.asarray(want["grad_norms"])
    dropped = {n for n, x in zip(names, g)
               if x < train.GRADIENT_FLOOR * np.median(g)}
    assert dropped == {"h_0/attn/qkv/bias_k", "h_1/attn/qkv/bias_k"}


def test_the_training_control_is_not_correct(sound_train, train_cell):
    """The reference in the program's place, computed in scaled 8-bit
    floats (the precision below bfloat16)."""
    want = sound_train[2]["reference"]
    control = train.reference_readings(train_cell, SEED, quant=True)
    checks = harness.judge(train.numbers(control, want), train_cell.limits)
    assert "grad_apart_median" in _failed(checks)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch, train_cell):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    real = DeepSpeedEngine.train_batch

    def unchanged(self, data_iter=None, batch=None):
        before = jax.tree.map(jnp.copy, self.state)
        loss = real(self, data_iter=data_iter, batch=batch)
        self.state = before
        return loss

    monkeypatch.setattr(DeepSpeedEngine, "train_batch", unchanged)
    line, checks, _ = measure(train_cell, SEED, 0.3, 0)
    assert json.loads(line)["correct"] is False
    assert {"grad_norm_gap", "change_norm_gap"} <= _failed(checks)
    by_name = {n: v for n, v, _, _ in checks}
    assert by_name["change_norm_gap"] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, train_cell):
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    real = DeepSpeedEngine.train_batch

    def half(self, data_iter=None, batch=None):
        batch = dict(next(data_iter))
        labels = np.array(batch["input_ids"])
        labels[len(labels) // 2:] = -100     # the mean is taken over the rest
        batch["labels"] = labels
        return real(self, batch=batch)

    monkeypatch.setattr(DeepSpeedEngine, "train_batch", half)
    line, checks, _ = measure(train_cell, SEED, 0.3, 0)
    assert json.loads(line)["correct"] is False
    assert {"grad_norm_gap", "grad_apart_median"} <= _failed(checks)


def test_the_mesh_path_on_four_virtual_devices():
    """Open question 1 (gpt2-xl ZeRO-3 over dp=4) lands as data: the same
    kind drives a sharded engine from a traffic file that says so."""
    cell = benchmark_tiny.cell("tiny-train-zero3-dp4")
    line, checks, out = measure(cell, SEED, 0.3, 0)
    result = json.loads(line)
    assert result["device"]["count"] == 4 == len(out["devices"])
    assert result["correct"] is True, checks
    assert out["records"]["compiles_in_window"] == 0


# ------------------------------------------------------------------- serving
@pytest.fixture(scope="module")
def serve_cell():
    return benchmark_tiny.cell("tiny-backlog")


@pytest.fixture(scope="module")
def sound_serve(serve_cell):
    return measure(serve_cell, SEED, 2.0, 0)


def test_a_sound_serving_run_is_correct(sound_serve):
    line, checks, out = sound_serve
    result = json.loads(line)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 20
    assert set(result["checks"]) == {"top_gap_max", "top_gap_mean",
                                     "served_wrong"}
    sample, rec = out["evidence"], out["records"]
    # every request that was served a token is compared, unfinished too
    assert len(sample) >= result["attempted"] + len(
        [n for n in rec["at_close"].values() if 0 < n]) - len(rec["at_close"])
    assert sum(len(t) for _, t in sample) >= rec["delivered"] > 1000
    assert max(len(p) + len(t) for p, t in sample) <= 64 + 48


def test_the_serving_control_is_not_correct(sound_serve, serve_cell):
    """At each position of the same prompts and tokens, the token that the
    scaled 8-bit float reference puts first."""
    control = serve_closed.numbers(serve_cell, SEED, sound_serve[2]["evidence"],
                                   quant=True)
    checks = harness.judge(control, serve_cell.limits)
    assert {"top_gap_max", "top_gap_mean"} <= _failed(checks)


def test_a_token_altered_where_it_is_produced_is_not_correct(monkeypatch,
                                                            serve_cell):
    from deepspeed_tpu.serving.runner import PagedGPT2Runner
    real = PagedGPT2Runner.decode_step
    calls = {"n": 0}

    def altered(self, *args, **kwargs):
        pools, tokens = real(self, *args, **kwargs)
        calls["n"] += 1
        if calls["n"] % 5 == 0:             # every fifth dispatch, every slot
            tokens = (tokens + 1) % self.cfg.vocab_size
        return pools, tokens

    monkeypatch.setattr(PagedGPT2Runner, "decode_step", altered)
    line, checks, _ = measure(serve_cell, SEED, 2.0, 0)
    assert json.loads(line)["correct"] is False
    assert {"top_gap_max", "top_gap_mean"} <= _failed(checks)


def test_a_number_without_a_limit_is_an_error_not_a_pass():
    with pytest.raises(KeyError):
        harness.judge({"a_new_number": 0.0}, {})
    assert harness.judge({"x": float("nan")}, {"x": {"limit": 1.0}})[0][3] \
        is False
