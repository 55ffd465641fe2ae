"""The ``granite_4_0_h`` reference, binding and metrics at a test size on the
CPU (``tiny_granite_4_0_h.json``: the shape of
``configs/granite-4.0-h-micro.json`` with every size cut): what the program
serves through its paged K/V pools and per-slot state against the plain
reference's full forward (its state-space mixer in the dual form); the
cell's kind through ``run.measure``; the new metric readers on canned
records. A CPU run yields counts and comparisons, never a speed."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, harness, program_spans
from benchmark.reference import granite_4_0_h as ref
from benchmark.run import measure

TINY = json.loads(Path(__file__).with_name(
    "tiny_granite_4_0_h.json").read_text())
FULL = json.loads((harness.HERE / "configs"
                   / "granite-4.0-h-micro.json").read_text())
SEED = 3_000_000_017


def tiny_cell(precision="bfloat16", **traffic):
    tr = {**TINY["traffic"]["tiny-backlog"], **traffic}
    return harness.Cell(
        name="tiny.granite_4_0_h", config={**TINY["config"],
                                           "precision": precision},
        traffic=tr, chips=1,
        end_to_end=({"name": "serve_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=(), limits=TINY["limits"]["serve"])


# ------------- (a) served through the pools and the per-slot state = reference
def _served_logits(precision, prompts, n_decode, block_size=8, chunk=7):
    """Logits at every position of every row: chunked prefill of each
    prompt alone (its slot's state carried from chunk to chunk), then
    ``n_decode`` decode steps of both slots together (teacher-forced), all
    through the server's one forward, its paged K/V pools and its state
    pools."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    config = {**TINY["config"], "precision": precision}
    dtype = getattr(jnp, precision)
    program = harness.load_named("programs", "granite_4_0_h")
    groups.destroy()
    groups.initialize(devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        program.model(config), dtype=dtype,
        params=ref.make_weights(ref.seed_words(SEED), ref.sizes(config),
                                dtype))
    srv = deepspeed_tpu.init_serving(engine=engine, config={"serving": {
        "max_batch": len(prompts), "block_size": block_size,
        "prefill_chunk": chunk, "max_model_len": 128}})
    runner, pools = srv.runner, srv.pools
    MB = srv.max_blocks_per_seq
    rng = np.random.default_rng(1)
    free = iter(rng.permutation(np.arange(1, srv.cache.num_blocks)))
    bt = np.zeros((len(prompts), MB), np.int32)
    for b, ids in enumerate(prompts):                   # scattered blocks
        for i in range(-(-len(ids) // block_size)):
            bt[b, i] = next(free)
    forward = jax.jit(runner._forward)
    logits = [[] for _ in prompts]
    n_prefill = [len(ids) - n_decode for ids in prompts]
    for b, ids in enumerate(prompts):
        for start in range(0, n_prefill[b], chunk):
            n = min(chunk, n_prefill[b] - start)
            tok = np.zeros((1, chunk), np.int32)
            tok[0, :n] = ids[start:start + n]
            idx = np.arange(chunk)
            pools, out, _ = forward(
                engine.params, {}, pools, jnp.asarray(bt[b:b + 1]),
                jnp.asarray([start]), jnp.asarray(tok),
                jnp.asarray(start + idx)[None], jnp.asarray(idx < n)[None],
                slot=jnp.int32(b))
            logits[b].append(np.asarray(out)[:n])
    for step in range(n_decode):
        pos = np.array([n + step for n in n_prefill], np.int32)
        tok = np.array([ids[p] for ids, p in zip(prompts, pos)], np.int32)
        pools, out, _ = forward(
            engine.params, {}, pools, jnp.asarray(bt), jnp.asarray(pos),
            jnp.asarray(tok)[:, None], jnp.asarray(pos)[:, None],
            jnp.ones((len(prompts), 1), bool))
        for b in range(len(prompts)):
            logits[b].append(np.asarray(out)[b:b + 1])
    srv.close()
    groups.destroy()
    return [np.concatenate(rows) for rows in logits], config


# float32 through the pools, the chunked scan and the per-token update
# against float32 at HIGHEST in the dual form: only the order of the sums
# differs (4.5e-8 at most here, where a position's logits have a standard
# deviation of 0.02 and reach 0.19)
TOLERANCE = 1e-6


@pytest.fixture(scope="module")
def two_slots():
    rng = np.random.default_rng(0)
    V = TINY["config"]["vocab_size"]
    # 43 and 41 tokens, the last 24 of each decoded: prefill of 19 and 17
    # in chunks of 7 (three each, the last part empty), blocks of 8 ending
    # elsewhere, and the two slots decode at positions 19.. and 17..
    return [rng.integers(0, V, n).astype(np.int32) for n in (43, 41)]


def test_served_logits_equal_the_reference_forward(two_slots):
    got, config = _served_logits("float32", two_slots, n_decode=24)
    for ids, mine in zip(two_slots, got):
        want = ref.full_forward(config, SEED, ids[None])[0]
        assert mine.shape == want.shape
        assert np.abs(mine - want).max() < TOLERANCE
        assert (mine.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_program_is_outside_that_tolerance(two_slots):
    got, config = _served_logits("bfloat16", two_slots, n_decode=24)
    want = ref.full_forward(config, SEED, two_slots[0][None])[0]
    assert 50 * TOLERANCE < np.abs(got[0] - want).max() < 0.002


def test_the_control_reads_far_from_the_reference(two_slots):
    config = TINY["config"]
    ids = two_slots[0][None]
    sound = ref.full_forward(config, SEED, ids)
    control = ref.full_forward(config, SEED, ids, quant=True)
    assert np.abs(control - sound).max() > 1000 * TOLERANCE


def test_the_binding_refuses_a_file_that_disagrees_with_the_program():
    program = harness.load_named("programs", "granite_4_0_h")
    config = TINY["config"]
    assert program.model(config).config.attention_layers == (1,)
    for change, match in (
            ({"assumed": {**config["assumed"], "kv_row_lanes": 64}},
             "lanes"),
            ({"assumed": {**config["assumed"], "state_dtype": "bfloat16"}},
             "state"),
            ({"num_hidden_layers": 5}, "layer_types names"),
            ({"position_embedding_type": "rope"}, "no positions")):
        with pytest.raises(ValueError, match=match):
            program.model({**config, **change})
    cfg = program.model(FULL).config
    assert (cfg.n_layer, cfg.attention_layers) == (40, (5, 15, 25, 35))
    assert (cfg.mamba_inner, cfg.conv_channels, cfg.head_dim) == (
        4096, 4352, 64)


# ------------------------------------ (i) the kind, through run.measure
def test_a_sound_run_is_correct_and_the_control_is_not():
    from benchmark.kinds import serve_closed
    cell = tiny_cell()
    line, checks, out = measure(cell, SEED, 1.0, 0)
    result = json.loads(line)
    assert result["correct"] is True, checks
    assert out["records"]["sample"]["slots_share"] == 1.0
    assert out["records"]["compiles_in_window"] == 0
    control = serve_closed.numbers(cell, SEED, out["evidence"], quant=True)
    failed = [n for n, v in control.items()
              if v > cell.limits[n]["limit"]]
    assert failed, control


@pytest.mark.parametrize("slot", [0, 3])
def test_a_wrong_token_in_one_slot_alone_is_not_correct(monkeypatch, slot):
    from deepspeed_tpu.serving.runner import PagedRunner
    real = PagedRunner.decode_step

    def altered(self, *args, **kwargs):
        pools, tokens = real(self, *args, **kwargs)
        tokens = tokens.at[:, slot].set(
            (tokens[:, slot] + 1) % self.cfg.vocab_size)
        return pools, tokens

    monkeypatch.setattr(PagedRunner, "decode_step", altered)
    cell = tiny_cell(check_requests_per_slot=1)
    line, checks, out = measure(cell, SEED, 1.0, 0)
    assert json.loads(line)["correct"] is False
    assert {n for n, _, _, ok in checks if not ok} \
        >= {"top_gap_max", "top_gap_mean"}


# --------------------------------------------------- the required operations
def test_required_operations_of_the_configuration_as_run():
    c = FULL
    mamba = 2048 * 8512 + 4 * 4352 + 4096 * 2048 + 3 * 2048 * 8192
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    assert ref._weights_a_token(c) == (mamba, attention)
    per_token = 2 * (36 * mamba + 4 * attention) + 36 * 5 * 64 * 64 * 128
    # one output token after a prompt of 10: positions 0..9 and a logit
    one = ref.serve_flops(c, 10, 0, 1)
    assert one == pytest.approx(10 * per_token + 2 * 100352 * 2048
                                + 4 * 4 * 32 * 64 * sum(range(10)))
    # the next two: positions 10 and 11
    more = ref.serve_flops(c, 10, 1, 3)
    assert more == pytest.approx(2 * per_token + 2 * 2 * 100352 * 2048
                                 + 4 * 4 * 32 * 64 * (10 + 11))
    assert ref.serve_flops(c, 10, 3, 3) == 0
    cost = ref.ssm_decode_cost(c, 10)
    assert cost == {"flops": 10 * 5 * 524288.0, "bytes": 10 * 4194304.0}
    assert cost["flops"] / cost["bytes"] == pytest.approx(0.625)


# ------------------------------------------- the readers, on canned records
def _ctx(monkeypatch, decode_spans, ops=None, config=FULL):
    """A traced window of 2 s with ``serving_decode`` spans of the given
    arguments, as the program's tracer would hold them."""
    events = [{"name": "serving_decode", "ph": "X", "ts": (10.1 + i) * 1e6,
               "dur": 1000.0, "pid": 1, "tid": 1, "args": args}
              for i, args in enumerate(decode_spans)]
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    cell = dataclasses.replace(tiny_cell(), config=config)

    class Trace:
        pass

    trace = Trace()
    trace.ops = ops or {}
    return {"cell": cell, "spans": [("window", 10.0, 10.0 + len(events) + 1)],
            "trace": trace, "device_kind": "TPU v5 lite",
            "records": {"window_s": 2.0, "shape_of": {7: (10, 5)},
                        "at_open": {7: 1}, "at_close": {7: 3}}}


def test_the_state_roofline_reader_counts_states_and_kernel_time(monkeypatch):
    spans = [{"batch": 64, "blocks_needed": 9, "blocks_visited": 9,
              "state_rows": 64 * 36}] * 2
    ops = {"ssm_decode": 0.04, "ssm_decode.7": 0.08, "paged_decode": 9.0}
    got = harness.load_reader("offline_ssm_decode_roofline")(
        _ctx(monkeypatch, spans, ops))
    n = 64 * 64 * 128
    least = max(2 * 2304 * 5 * n / 197e12, 2 * 2304 * 8 * n / 819e9)
    assert got == pytest.approx(100 * least / 0.12)
    assert 0 < got < 100
    # no kernel on the trace, or no state counted (the parent, GPT-2):
    # nothing to read
    reader = harness.load_reader("offline_ssm_decode_roofline")
    assert reader(_ctx(monkeypatch, spans, {"paged_decode": 1.0})) is None
    assert reader(_ctx(monkeypatch, [{"batch": 4, "blocks_needed": 1,
                                      "blocks_visited": 1}], ops)) is None


def test_the_step_share_reader_charges_the_windows_tokens(monkeypatch):
    ctx = _ctx(monkeypatch, [])
    got = harness.load_reader("serve_mfu")(ctx)
    need = ref.serve_flops(FULL, 10, 1, 3)
    assert got == pytest.approx(100 * need / 2.0 / 197e12)
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
