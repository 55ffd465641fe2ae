"""Speculative decoding tests — draft/verify over the paged KV
(serving/speculative.py + the server's speculative dispatch path).

The acceptance discipline under test is PR-6's, extended: with
``acceptance="exact"`` the speculative engine must be bit-exact against
the non-speculative path for greedy AND sampled traffic (the shared
position-folded RNG schedule in serving/sampling.py makes the verify
program compare the SAME draw sequential decoding would have made), it
must compose with int8 weights + int8 KV and with the COW prefix cache,
survive preemption, and hold steady state at exactly {1 draft, 1 verify}
compiled programs with ZERO decode signatures and zero retraces.
Rejection cost is booked, never hidden: the registry counters, the
per-request acceptance rate, and the observatory's ``speculation_waste``
rule -> guardian one-way fallback all get exercised here.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                          DeepSpeedServingConfig)
from deepspeed_tpu.serving.sampling import (fold_position_lanes,
                                            make_rng_lane)
from deepspeed_tpu.serving.scheduler import Request
from deepspeed_tpu.serving.server import ServingEngine
from deepspeed_tpu.serving.speculative import (SpeculativeDecoder,
                                               default_draft_layers,
                                               validate_draft_params)
from deepspeed_tpu.telemetry.metrics import MetricsRegistry
from deepspeed_tpu.utils import groups

SPEC_COMPILE = {"decode_signatures": 0, "prefill_signatures": 1,
                "retraces": 0, "draft_signatures": 1,
                "verify_signatures": 1}


def _make_engine(seed=0, n_layer=4, kv="auto", dtype=jnp.float32,
                 n_embd=32, n_head=2):
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=n_embd,
                     n_layer=n_layer, n_head=n_head, kv_cache_dtype=kv)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(seed),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return cfg, deepspeed_tpu.init_inference(model, params=params,
                                             dtype=dtype)


def _spec_cfg(k=3, extra=None, spec_extra=None):
    cfg = {"max_batch": 3, "block_size": 8, "prefill_chunk": 6,
           "speculative": dict({"enabled": True, "k": k,
                                "draft_layers": 2}, **(spec_extra or {}))}
    cfg.update(extra or {})
    return cfg


def _baseline(eng, prompt, n_new):
    out = eng.generate(jnp.asarray(prompt, jnp.int32)[None],
                       max_new_tokens=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


@pytest.fixture(scope="module")
def tiny():
    return _make_engine()


# -------------------------------------------------------- greedy parity
def test_greedy_parity_and_two_programs(tiny):
    """Heterogeneous greedy trace through the speculative path: every
    token bit-exact vs batch-synchronous generate(), steady state at
    exactly {1 draft, 1 verify} programs / 0 decode signatures /
    0 retraces, allocator clean."""
    cfg, eng = tiny
    srv = ServingEngine(eng, config=_spec_cfg(),
                        registry=MetricsRegistry())
    rng = np.random.default_rng(7)
    cases = [(1, 5), (11, 3), (30, 9), (7, 5), (19, 2), (4, 7)]
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p, _ in cases]
    rids = [srv.submit(p, max_new_tokens=g)
            for p, (_, g) in zip(prompts, cases)]
    outs = {o.req_id: o for o in srv.serve_forever()}
    for rid, p, (_, g) in zip(rids, prompts, cases):
        assert outs[rid].tokens == _baseline(eng, p, g), f"req {rid}"
    assert srv.compile_stats() == SPEC_COMPILE
    srv.cache.allocator.check_consistency()
    assert srv.cache.allocator.num_allocated == 0
    # the acceptance counters are live and consistent
    snap = srv.registry.snapshot()
    drafted = snap["serving_spec_drafted_total"][0]["value"]
    accepted = snap["serving_spec_accepted_total"][0]["value"]
    assert drafted > 0 and 0 < accepted <= drafted
    assert snap["serving_spec_acceptance_rate"][0]["value"] == \
        pytest.approx(accepted / drafted)


def test_sampled_mixed_parity_vs_nonspec_engine(tiny):
    """Mixed greedy/sampled traffic: with acceptance="exact" the
    speculative engine must reproduce the NON-speculative serving
    engine's streams token-for-token — the shared position-folded RNG
    schedule means the verify program replays the same draws."""
    cfg, eng = tiny
    rng = np.random.default_rng(23)
    reqs = [  # (prompt_len, gen, temperature, top_p, seed)
        (9, 6, 0.0, 1.0, 0), (5, 8, 0.9, 0.8, 3),
        (14, 5, 0.7, 1.0, 4), (3, 7, 1.1, 0.6, 9)]
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p, *_ in reqs]

    def serve(spec):
        srv = ServingEngine(
            eng, config=_spec_cfg() if spec else {"max_batch": 3,
                                                  "block_size": 8,
                                                  "prefill_chunk": 6},
            registry=MetricsRegistry())
        rids = [srv.submit(p, max_new_tokens=g, temperature=t, top_p=tp,
                           seed=s)
                for p, (_, g, t, tp, s) in zip(prompts, reqs)]
        outs = {o.req_id: o for o in srv.serve_forever()}
        return [outs[r].tokens for r in rids]

    assert serve(spec=True) == serve(spec=False)


def test_int8_weights_int8_kv_parity():
    """The bench headline combo composes: int8 weight storage + int8
    lane-scale KV + speculation, still bit-exact vs the same engine's
    non-speculative serving path."""
    cfg, eng = _make_engine(seed=2, kv="int8", dtype=jnp.int8)
    assert eng.quant_scales is not None
    rng = np.random.default_rng(11)
    reqs = [(13, 6, 0.0, 1.0, 0), (5, 4, 0.8, 0.9, 7), (21, 5, 0.0, 1.0, 0)]
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p, *_ in reqs]

    def serve(spec):
        srv = ServingEngine(
            eng, config=_spec_cfg() if spec else {"max_batch": 2,
                                                  "block_size": 8},
            registry=MetricsRegistry())
        assert srv.cache.int8_kv
        rids = [srv.submit(p, max_new_tokens=g, temperature=t, top_p=tp,
                           seed=s)
                for p, (_, g, t, tp, s) in zip(prompts, reqs)]
        outs = {o.req_id: o for o in srv.serve_forever()}
        if spec:
            assert srv.compile_stats() == SPEC_COMPILE
        return [outs[r].tokens for r in rids]

    assert serve(spec=True) == serve(spec=False)


@pytest.mark.parametrize("kv", ["auto", "int8"], ids=["kv-float", "kv-int8"])
def test_lanes_that_do_not_divide_parity(kv):
    """5 heads of 64 (2.5 lanes of 128: the pools' rows are padded to
    384): the draft's write over a layer prefix, the verify read and its
    write of all layers at K+1 positions serve generate()'s greedy
    tokens one for one."""
    cfg, eng = _make_engine(seed=4, kv=kv, n_embd=320, n_head=5)
    srv = ServingEngine(eng, config=_spec_cfg(), registry=MetricsRegistry())
    assert srv.cache.row_width == 384
    rng = np.random.default_rng(31)
    cases = [(11, 6), (3, 9), (22, 4)]
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p, _ in cases]
    rids = [srv.submit(p, max_new_tokens=g)
            for p, (_, g) in zip(prompts, cases)]
    outs = {o.req_id: o for o in srv.serve_forever()}
    for rid, p, (_, g) in zip(rids, prompts, cases):
        assert outs[rid].tokens == _baseline(eng, p, g), (kv, rid)
    assert srv.compile_stats() == SPEC_COMPILE
    assert not np.asarray(srv.pools["k"])[..., 320:].any()


def test_prefix_cache_composition(tiny):
    """Speculation over COW-forked prefix blocks: the draft/verify KV
    writes land only at positions >= cached_len, so shared blocks stay
    clean — cache hits plus bit-exact greedy parity plus a drained
    allocator."""
    cfg, eng = tiny
    srv = ServingEngine(
        eng, config=_spec_cfg(extra={"prefix_cache": {"enabled": True}}),
        registry=MetricsRegistry())
    rng = np.random.default_rng(31)
    head = rng.integers(0, cfg.vocab_size, (24,)).astype(np.int32)
    tails = [rng.integers(0, cfg.vocab_size, (t,)).astype(np.int32)
             for t in (3, 5, 7, 4)]
    prompts = [np.concatenate([head, t]) for t in tails]
    # first wave seeds the index, second wave hits it
    for wave in range(2):
        rids = [srv.submit(p, max_new_tokens=6) for p in prompts]
        outs = {o.req_id: o for o in srv.serve_forever()}
        for rid, p in zip(rids, prompts):
            assert outs[rid].tokens == _baseline(eng, p, 6), (wave, rid)
    pc = srv.cache.prefix_cache
    assert pc.stats()["hits"] > 0
    assert srv.compile_stats() == SPEC_COMPILE
    # after drain the only references left are the index's own: cache-
    # only blocks, reclaimable on demand, zero once dropped
    assert pc.shared_blocks() == 0
    pc.drop_all()
    srv.cache.allocator.check_consistency()
    assert srv.cache.allocator.num_allocated == 0


def test_preemption_under_speculation_parity():
    """An undersized pool forces eviction mid-generation while the
    speculative path is live; recompute-on-resume must still reproduce
    the uncontended greedy tokens exactly."""
    cfg, eng = _make_engine(seed=1, n_layer=2)
    srv = ServingEngine(
        eng, config=_spec_cfg(extra={"max_batch": 2, "num_blocks": 7}),
        registry=MetricsRegistry())
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (15,)).astype(np.int32)
               for _ in range(2)]
    rids = [srv.submit(p, max_new_tokens=20) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    assert srv.scheduler.preemptions_total >= 1, \
        "scenario must actually exercise eviction"
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 20)
    srv.cache.allocator.check_consistency()
    assert srv.cache.allocator.num_allocated == 0


# ----------------------------------------------------- explicit draft
def _bad_draft(eng, row=7):
    """A deliberately BAD explicit draft: the target's params with the
    final LN collapsed to a constant output of ``wte[row]``, so the
    draft greedily predicts that row regardless of input while the
    random-init target copies its input token (tied near-orthogonal
    embeddings make the self-dot dominate the logits). A second random
    init does NOT work here: both seeds are input-copiers, so they
    agree ~100% — and any permutation of the tied wte permutes inputs
    and outputs together, leaving predictions fixed."""
    params = dict(jax.device_get(eng.params))
    wte = np.asarray(params["wte"])
    params["ln_f"] = {"scale": np.zeros_like(wte[row]),
                      "bias": wte[row].copy()}
    return params


def test_explicit_draft_params_rejections_booked(tiny):
    """Exact acceptance keeps parity even when the draft is hostile,
    and the rejection cost shows up in the counters and the ledger's
    drafted_rejected category instead of being hidden."""
    cfg, eng = tiny
    draft_params = _bad_draft(eng)
    srv = ServingEngine(
        eng,
        config=_spec_cfg(extra={"observability": {
            "enabled": True, "window": 4, "ttft_slo_ms": 1e12,
            "preemption_thrash": 10 ** 9, "no_progress_steps": 10 ** 9,
            "snapshot_file": "/tmp/test_spec_health.json"}}),
        registry=MetricsRegistry(), draft_params=draft_params)
    rng = np.random.default_rng(41)
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p in (9, 4, 17)]
    rids = [srv.submit(p, max_new_tokens=8) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 8)
    snap = srv.registry.snapshot()
    rejected = snap["serving_spec_rejected_total"][0]["value"]
    assert rejected > 0, "a random draft must miss"
    units, _ = srv.observatory.ledger.totals()
    assert units["drafted_rejected"] > 0


def test_validate_draft_params_errors(tiny):
    cfg, eng = tiny
    target = jax.device_get(eng.params)
    good = dict(target)
    validate_draft_params(good, target, 2)          # no raise
    with pytest.raises(ValueError, match="missing 'wte'"):
        validate_draft_params({"wpe": 0, "ln_f": 0}, target, 1)
    bad_wte = dict(good)
    bad_wte["wte"] = np.zeros((7, 3), np.float32)
    with pytest.raises(ValueError, match="vocab and embedding width"):
        validate_draft_params(bad_wte, target, 1)
    shallow = {k: v for k, v in good.items() if k != "h_3"}
    with pytest.raises(ValueError, match="no h_3"):
        validate_draft_params(shallow, target, 4)


def test_default_draft_layers_floor():
    assert default_draft_layers(2) == 1
    assert default_draft_layers(8) == 2
    assert default_draft_layers(48) == 12


# --------------------------------------------------- config validation
def test_config_validation_errors():
    for bad in ({"k": 0}, {"acceptance": "hopeful"},
                {"typical_threshold": 0.0}, {"typical_threshold": 1.5},
                {"acceptance_floor": -0.1}, {"acceptance_floor": 1.5},
                {"draft_model": 7}):
        with pytest.raises(DeepSpeedConfigError):
            DeepSpeedServingConfig(
                {"serving": {"speculative": dict({"enabled": True}, **bad)}})
    ok = DeepSpeedServingConfig(
        {"serving": {"speculative": {"enabled": True, "k": 5,
                                     "acceptance": "typical"}}})
    assert ok.speculative.enabled and ok.speculative.k == 5


def test_env_override_toggles(monkeypatch):
    monkeypatch.setenv("DS_SERVING_SPEC", "1")
    on = DeepSpeedServingConfig({"serving": {}})
    assert on.speculative.enabled is True
    monkeypatch.setenv("DS_SERVING_SPEC", "0")
    off = DeepSpeedServingConfig(
        {"serving": {"speculative": {"enabled": True}}})
    assert off.speculative.enabled is False


# -------------------------------------------------- shared RNG schedule
def test_fold_position_lanes_matches_scalar_fold_in():
    """The one randomness schedule both the decode scan and the verify
    program use: vmapped fold must equal per-element jax.random.fold_in
    so a token's draw depends only on (seed, position)."""
    lanes = np.stack([make_rng_lane(s) for s in (0, 7, 123)])
    positions = jnp.asarray([3, 0, 55], jnp.int32)
    folded = fold_position_lanes(jnp.asarray(lanes), positions)
    for i, (lane, pos) in enumerate(zip(lanes, (3, 0, 55))):
        want = jax.random.fold_in(jnp.asarray(lane, jnp.uint32), pos)
        assert np.array_equal(np.asarray(folded[i]), np.asarray(want)), i


# ----------------------------------------------- typical acceptance mode
def test_typical_mode_greedy_slots_stay_exact(tiny):
    """acceptance="typical" relaxes SAMPLED slots only; an all-greedy
    trace must still be bit-exact vs generate()."""
    cfg, eng = tiny
    srv = ServingEngine(
        eng, config=_spec_cfg(spec_extra={"acceptance": "typical",
                                          "typical_threshold": 0.3}),
        registry=MetricsRegistry())
    rng = np.random.default_rng(53)
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p in (6, 12, 3)]
    rids = [srv.submit(p, max_new_tokens=7) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 7)
    assert srv.compile_stats() == SPEC_COMPILE


# ------------------------------------------- waste rule -> guardian off
def test_speculation_waste_disables_via_guardian(tiny):
    """The full degradation loop: a bad draft + acceptance_floor arms
    the observatory's speculation_waste rule, its anomaly drains through
    the guardian's serving tick, the guardian's one-shot action turns
    speculation OFF (one-way), and the engine keeps serving through the
    plain decode program with parity intact."""
    from deepspeed_tpu.runtime.guardian import Guardian
    cfg, eng = tiny
    draft_params = _bad_draft(eng)
    guardian = Guardian(enabled=True, action_cooldown_steps=0,
                        emergency_checkpoint=False, journal_path=None)
    srv = ServingEngine(
        eng,
        config=_spec_cfg(
            spec_extra={"acceptance_floor": 0.95},
            extra={"observability": {
                "enabled": True, "window": 4,
                "warmup_windows": 0, "ttft_slo_ms": 1e12,
                "preemption_thrash": 10 ** 9,
                "no_progress_steps": 10 ** 9,
                "snapshot_file": "/tmp/test_spec_waste_health.json"}}),
        registry=MetricsRegistry(), guardian=guardian,
        draft_params=draft_params)
    assert guardian.spec_disable_fn is not None
    rng = np.random.default_rng(61)
    prompts = [rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32)
               for p in (9, 5, 13, 7)]
    rids = [srv.submit(p, max_new_tokens=12) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    assert srv._spec_disabled_rule == "speculation_waste", (
        "the windowed acceptance collapse must reach the guardian and "
        "turn speculation off")
    assert guardian.action_counts.get("serving_spec_disable") == 1
    snap = srv.registry.snapshot()
    assert snap["serving_speculation_disabled"][0]["value"] == 1
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 12)
    # serving continued through the fallback: the plain decode program
    # exists alongside the draft/verify pair
    stats = srv.compile_stats()
    assert stats["draft_signatures"] == 1
    assert stats["verify_signatures"] == 1
    assert stats["decode_signatures"] == 1 and stats["retraces"] == 0
    # one-way: a second disable attempt is a no-op
    srv._disable_speculation("again")
    assert srv._spec_disabled_rule == "speculation_waste"
    # new traffic keeps flowing
    extra = srv.submit(prompts[0], max_new_tokens=4)
    outs2 = {o.req_id: o for o in srv.serve_forever()}
    assert outs2[extra].tokens == _baseline(eng, prompts[0], 4)


# ------------------------------------------------- per-request counters
def test_request_spec_acceptance_rate_property():
    r = Request(req_id=0, prompt=[1, 2], max_new_tokens=4)
    assert r.spec_acceptance_rate is None
    r.spec_drafted, r.spec_accepted = 10, 7
    assert r.spec_acceptance_rate == pytest.approx(0.7)


def test_decoder_rejects_bad_construction(tiny):
    cfg, eng = tiny
    srv = ServingEngine(eng, config=_spec_cfg(),
                        registry=MetricsRegistry())
    with pytest.raises(AssertionError):
        SpeculativeDecoder(srv.runner, k=0)
    with pytest.raises(AssertionError):
        SpeculativeDecoder(srv.runner, k=2, acceptance="maybe")
    with pytest.raises(AssertionError):
        SpeculativeDecoder(srv.runner, k=2, draft_layers=99)
