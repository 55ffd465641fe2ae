"""The ``dots_vlm1`` reference, binding and metrics at a test size on the CPU
(``tiny_dots_vlm1.json``: the shape of ``configs/dots.vlm1.inst.json`` with
every size cut): what the program serves through its paged latent cache
against the plain reference's full forward; the shares of the experts
against the uncut layer; the cell's kind through ``run.measure``; the new
metric readers on canned records. A CPU run yields counts and comparisons,
never a speed."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_mla_moe, harness, program_spans
from benchmark.reference import dots_vlm1 as ref
from benchmark.run import measure

TINY = json.loads(Path(__file__).with_name("tiny_dots_vlm1.json").read_text())
SEED = 3_000_000_011


def tiny_cell(precision="bfloat16", **traffic):
    tr = {**TINY["traffic"]["tiny-documents"], **traffic}
    return harness.Cell(
        name="tiny.dots_vlm1", config={**TINY["config"],
                                       "precision": precision},
        traffic=tr, chips=1,
        end_to_end=({"name": "serve_tokens_per_s", "unit": "tokens/s"},
                    {"name": "setup_s", "unit": "s"}),
        per_layer=(), limits=TINY["limits"]["serve"])


# ------------------- (a) served through the paged latent cache = reference
def _served_logits(precision, prompts, n_decode, block_size=8, chunk=6):
    """Logits at every position of every row: chunked prefill of each
    prompt alone, then ``n_decode`` decode steps of both slots together
    (teacher-forced), all through the server's one forward and its paged
    latent pool."""
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups
    config = {**TINY["config"], "precision": precision}
    dtype = getattr(jnp, precision)
    program = harness.load_named("programs", "dots_vlm1")
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
                jnp.asarray(start + idx)[None], jnp.asarray(idx < n)[None])
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


# float32 through the paged path against float32 at HIGHEST: only the order
# of the sums differs; logits have a spread of about 0.15 here
TOLERANCE = 2e-5


@pytest.fixture(scope="module")
def two_slots():
    rng = np.random.default_rng(0)
    V = TINY["config"]["vocab_size"]
    # 45 and 70 tokens: chunks of 6 and blocks of 8 end at different places,
    # the last chunks are partial, and the slots decode at positions 38.. and
    # 63.., across the block boundaries at 40 and 64
    return [rng.integers(0, V, n).astype(np.int32) for n in (45, 70)]


def test_served_logits_equal_the_reference_forward(two_slots):
    got, config = _served_logits("float32", two_slots, n_decode=7)
    for ids, mine in zip(two_slots, got):
        want = ref.full_forward(config, SEED, ids[None])[0]
        assert mine.shape == want.shape
        assert np.abs(mine - want).max() < TOLERANCE
        assert (mine.argmax(-1) == want.argmax(-1)).all()


def test_a_bfloat16_program_is_outside_that_tolerance(two_slots):
    got, config = _served_logits("bfloat16", two_slots, n_decode=7)
    want = ref.full_forward(config, SEED, two_slots[0][None])[0]
    assert 50 * TOLERANCE < np.abs(got[0] - want).max() < 0.05


def test_the_control_reads_far_from_the_reference(two_slots):
    config = TINY["config"]
    ids = two_slots[0][None]
    sound = ref.full_forward(config, SEED, ids)
    control = ref.full_forward(config, SEED, ids, quant=True)
    assert np.abs(control - sound).max() > 1000 * TOLERANCE


# -------------------- (d) the shares add up to the uncut layer
def test_all_shares_and_the_shared_expert_once_give_the_uncut_layer():
    """16 routed experts over 4 chips of 4: the routed parts that the
    program's expert layer gives on each share, plus the shared expert
    counted once, are the reference's whole layer with every expert
    held."""
    from deepspeed_tpu.moe.held_experts import held_expert_mlp, route
    config = TINY["config"]
    X = config["published"]["n_routed_experts"]
    sz = ref.sizes(config)._replace(held=(0, X))
    p = ref.layer_weights(ref.seed_words(SEED), 1, sz, True)["moe"]
    h = jax.random.normal(jax.random.PRNGKey(3), (37, sz.E), jnp.float32)
    want = np.asarray(ref._moe(h, p, sz, False))
    chosen, weights = route(h, p["router"], p["router_bias"], k=sz.k,
                            n_group=sz.n_group, topk_group=sz.topk_group,
                            scale=sz.route_scale)
    total = np.asarray(ref._swiglu(h, p["shared"]["gate"], p["shared"]["up"],
                                   p["shared"]["down"], False))
    pairs = 0
    for first in range(0, X, 4):
        share = {name: w[first:first + 4]
                 for name, w in p["experts"].items()}
        part, counts = held_expert_mlp(h, chosen, weights, share, first,
                                       jnp.ones((37,), bool))
        assert np.abs(np.asarray(part)).max() > 0
        total = total + np.asarray(part)
        pairs += int(counts[0])
        assert int(counts[0]) + int(counts[1]) == 37 * sz.k
    assert pairs == 37 * sz.k           # every choice is held by one share
    np.testing.assert_allclose(total, want, atol=1e-5)
    # and one share alone is what the reference computes when given it
    alone = ref._moe(h, {**p, "experts": {n: w[4:8] for n, w in
                                          p["experts"].items()}},
                     sz._replace(held=(4, 8)), False)
    assert np.abs(np.asarray(alone) - want).max() > 1e-3


def test_the_binding_refuses_a_file_that_disagrees_with_the_program():
    program = harness.load_named("programs", "dots_vlm1")
    config = TINY["config"]
    assert program.model(config).config.experts_held == (4, 8)
    for change, match in (
            ({"assumed": {**config["assumed"], "latent_row_lanes": 64}},
             "lanes"),
            ({"assumed": {**config["assumed"], "softmax_scale": 0.2}},
             "scales scores"),
            ({"n_routed_experts": 5}, "routed experts")):
        with pytest.raises(ValueError, match=match):
            program.model({**config, **change})
    full = json.loads((harness.HERE / "configs"
                       / "dots.vlm1.inst.json").read_text())
    cfg = program.model(full).config
    assert (cfg.n_routed_experts, cfg.n_held, cfg.num_experts_per_tok) == (
        256, 16, 8)
    assert cfg.latent_width == 576 and cfg.n_layer == 6


# ------------------------------- (f) the kind, through run.measure
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
    c = json.loads((harness.HERE / "configs"
                    / "dots.vlm1.inst.json").read_text())
    attn = (7168 * 1536 + 1536 * 128 * 192 + 7168 * 576 + 512 * 128 * 256
            + 16384 * 7168)
    expert = 3 * 7168 * 2048
    assert flops_mla_moe._attention_weights(c) == attn == 187_105_280
    want = (6 * attn + 3 * 7168 * 18432
            + 5 * (7168 * 256 + expert + 8 * 16 / 256 * expert))
    assert flops_mla_moe.weights_a_token(c) == want
    assert flops_mla_moe.attention_flops_a_context_token(c) == \
        6 * 2 * 128 * (192 + 128)
    # one output token after a prompt of 10: positions 0..9 and a logit
    one = flops_mla_moe.serve_flops(c, 10, 0, 1)
    assert one == pytest.approx(10 * 2 * want + 2 * 16160 * 7168
                                + 6 * 2 * 128 * 320 * sum(range(10)))
    # the next two: positions 10 and 11
    more = flops_mla_moe.serve_flops(c, 10, 1, 3)
    assert more == pytest.approx(2 * 2 * want + 2 * 2 * 16160 * 7168
                                 + 6 * 2 * 128 * 320 * (10 + 11))
    assert flops_mla_moe.serve_flops(c, 10, 3, 3) == 0
    cost = flops_mla_moe.latent_decode_cost(c, 10, 16)
    assert cost == {"flops": 160 * 278_528.0, "bytes": 160 * 1152.0}
    assert cost["flops"] / cost["bytes"] == pytest.approx(241.8, abs=0.1)


# ------------------------------------------- the readers, on canned records
def _ctx(monkeypatch, decode_spans, ops=None):
    """A traced window of 2 s with ``serving_decode`` spans of the given
    arguments, as the program's tracer would hold them."""
    events = [{"name": "serving_decode", "ph": "X", "ts": (10.1 + i) * 1e6,
               "dur": 1000.0, "pid": 1, "tid": 1, "args": args}
              for i, args in enumerate(decode_spans)]
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    cell = dataclasses.replace(
        tiny_cell(), config=json.loads(
            (harness.HERE / "configs" / "dots.vlm1.inst.json").read_text()))

    class Trace:
        pass

    trace = Trace()
    trace.ops = ops or {}
    return {"cell": cell, "spans": [("window", 10.0, 10.0 + len(events) + 1)],
            "trace": trace, "device_kind": "TPU v5 lite",
            "records": {"window_s": 2.0, "shape_of": {7: (10, 5)},
                        "at_open": {7: 1}, "at_close": {7: 3}}}


def test_the_expert_readers_read_the_spans_counts(monkeypatch):
    spans = [{"batch": 4, "blocks_needed": 9, "blocks_visited": 9,
              "pairs_held": 10, "pairs_absent": 150, "pairs_max": 3},
             {"batch": 4, "blocks_needed": 11, "blocks_visited": 11,
              "pairs_held": 22, "pairs_absent": 298, "pairs_max": 5},
             {"batch": 4, "blocks_needed": 1, "blocks_visited": 1}]
    ctx = _ctx(monkeypatch, spans)
    share = harness.load_reader("offline_expert_pairs_held_share")(ctx)
    assert share == pytest.approx(100 * 32 / 480)
    imbalance = harness.load_reader("offline_expert_load_imbalance")(ctx)
    assert imbalance == pytest.approx(100 * 8 / (32 / 16))
    # a program that counts no pairs (the parent, GPT-2) gives nothing
    none = _ctx(monkeypatch, [spans[2]])
    assert harness.load_reader("offline_expert_pairs_held_share")(none) is None
    assert harness.load_reader("offline_expert_load_imbalance")(none) is None


def test_the_latent_roofline_reader_counts_rows_and_kernel_time(monkeypatch):
    spans = [{"batch": 4, "blocks_needed": 1000, "blocks_visited": 1000}] * 2
    ops = {"paged_decode": 0.004, "paged_decode.3": 0.004, "fusion.1": 9.0}
    ctx = _ctx(monkeypatch, spans, ops)
    got = harness.load_reader("offline_latent_decode_roofline")(ctx)
    tokens = 2000 * 16 * 6
    least = max(tokens * 278_528 / 197e12, tokens * 1152 / 819e9)
    assert got == pytest.approx(100 * least / 0.008)
    assert 0 < got < 100
    # no kernel on the trace, or no blocks counted: nothing to read
    assert harness.load_reader("offline_latent_decode_roofline")(
        _ctx(monkeypatch, spans, {"fusion.1": 1.0})) is None
    assert harness.load_reader("offline_latent_decode_roofline")(
        _ctx(monkeypatch, [], ops)) is None


def test_the_step_share_reader_charges_the_windows_tokens(monkeypatch):
    ctx = _ctx(monkeypatch, [])
    got = harness.load_reader("dots_serve_step_mfu")(ctx)
    need = flops_mla_moe.serve_flops(ctx["cell"].config, 10, 1, 3)
    assert got == pytest.approx(100 * need / 2.0 / 197e12)
