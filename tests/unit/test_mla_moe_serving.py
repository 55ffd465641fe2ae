"""The latent-attention model with held experts, served (models/mla_moe.py,
moe/held_experts.py, the second block of serving/runner.py), at a tiny size
on the CPU: the absorbed attention over the paged latent pool against the
expanded one, the router against a literal transcription, the grouped
expert product against a loop, the YaRN frequencies against their closed
form, and the refusals at construction. The comparison with the plain
reference is tests/benchmark/test_benchmark_dots_vlm1.py's."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.mla_moe import (MLAMoEConfig, MLAMoEForCausalLM,
                                          init_params, yarn_inv_freq)
from deepspeed_tpu.moe.held_experts import (group_limited_topk,
                                            held_expert_mlp, route)
from deepspeed_tpu.serving import paged_attention as pa
from deepspeed_tpu.serving.kv_cache import PagedKVCache


def tiny_config(**changes):
    base = dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        first_k_dense_replace=1, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16, experts_held=(4, 8),
        num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=256, rope_factor=40.0,
        rope_original_max_position=64, rope_mscale=1.0,
        rope_mscale_all_dim=1.0)
    base.update(changes)
    return MLAMoEConfig(**base)


def _rnd(seed, shape, scale=1.0, dtype=jnp.float32):
    return (scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                      jnp.float32)).astype(dtype)


# ------------------------------------------------------------ (e) YaRN
def test_yarn_frequencies_follow_the_closed_form():
    """theta 10,000, 64 rotary dims, factor 40 over 4,096: ``cd(32) = 10.47``
    and ``cd(1) = 22.5``, so pairs 0..10 keep their frequency, pairs 23..31
    have it divided by 40, and between them the ramp is linear."""
    cfg = tiny_config(qk_rope_head_dim=64, rope_original_max_position=4096)
    got = yarn_inv_freq(cfg)
    f = np.array([10000.0 ** (-2 * i / 64) for i in range(32)])

    def cd(n):
        return 64 * math.log(4096 / (2 * math.pi * n)) / (2 * math.log(10000))

    low, high = max(math.floor(cd(32)), 0), min(math.ceil(cd(1)), 63)
    assert (low, high) == (10, 23)
    want = np.array([
        f[i] * (1 - min(max((i - low) / (high - low), 0), 1))
        + f[i] / 40 * min(max((i - low) / (high - low), 0), 1)
        for i in range(32)])
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[:11], f[:11], rtol=1e-6)
    np.testing.assert_allclose(got[23:], f[23:] / 40, rtol=1e-6)
    assert got.dtype == np.float32
    # m = 0.1 ln(40) + 1; the scale on the scores is 192^-1/2 m^2
    full = tiny_config(qk_nope_head_dim=128, qk_rope_head_dim=64)
    assert full.softmax_scale == pytest.approx(
        192 ** -0.5 * 1.3688879454 ** 2, rel=1e-9)
    assert full.rope_cos_sin_scale == 1.0
    assert tiny_config(rope_factor=1.0).softmax_scale == 24 ** -0.5


# ---------------------------------------------------------- (c) router
def _route_literally(h, router, bias, k, n_group, topk_group, scale):
    """The published router, one token at a time, in numpy float64."""
    chosen, weights = [], []
    for x in np.asarray(h, np.float64):
        sig = 1.0 / (1.0 + np.exp(-(x @ np.asarray(router, np.float64))))
        choice = sig + np.asarray(bias, np.float64)
        per = len(choice) // n_group
        group_score = [np.sort(choice[g * per:(g + 1) * per])[-2:].sum()
                       for g in range(n_group)]
        kept = np.argsort(group_score)[::-1][:topk_group]
        inside = [e for e in range(len(choice)) if e // per in kept]
        top = sorted(inside, key=lambda e: -choice[e])[:k]
        w = np.array([sig[e] for e in top])          # the bias is NOT in it
        chosen.append(top)
        weights.append(w / (w.sum() + 1e-20) * scale)
    return np.array(chosen), np.array(weights)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_a_literal_transcription(seed):
    N, E, X = 24, 32, 32
    h = _rnd(seed, (N, E))
    router = _rnd(seed + 10, (E, X), 0.3)
    bias = _rnd(seed + 20, (X,), 0.05)
    kw = dict(k=6, n_group=8, topk_group=4, scale=2.5)
    chosen, weights = route(h, router, bias, **kw)
    want_c, want_w = _route_literally(h, router, bias, **kw)
    for got_row, got_w, row, w in zip(np.asarray(chosen), np.asarray(weights),
                                      want_c, want_w):
        order = np.argsort(got_row)
        np.testing.assert_array_equal(got_row[order], np.sort(row))
        np.testing.assert_allclose(got_w[order], w[np.argsort(row)],
                                   rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 2.5, rtol=1e-5)


def test_the_bias_changes_the_choice_and_not_the_weights():
    N, E, X = 16, 32, 16
    h, router = _rnd(3, (N, E)), _rnd(4, (E, X), 0.3)
    kw = dict(k=2, n_group=4, topk_group=2, scale=1.0)
    plain, _ = route(h, router, jnp.zeros((X,)), **kw)
    # lift an expert that no token chose by itself: now every token does
    chosen_by_none = sorted(set(range(X)) - set(np.asarray(plain).ravel()))
    if not chosen_by_none:
        pytest.skip("every expert was chosen")
    lifted = chosen_by_none[0]
    bias = jnp.zeros((X,)).at[lifted].set(5.0)
    biased, w_biased = route(h, router, bias, **kw)
    assert (np.asarray(biased) == lifted).any(1).all()
    assert not (np.asarray(plain) == lifted).any()
    # its weight is its sigmoid score's share, not the biased score's
    want_c, want_w = _route_literally(h, router, bias, **kw)
    at = np.asarray(biased) == lifted
    np.testing.assert_allclose(np.asarray(w_biased)[at],
                               want_w[want_c == lifted], rtol=1e-5)
    assert (np.asarray(w_biased)[at] < 1.0).all()


def test_group_limited_topk_stays_inside_the_kept_groups():
    choice = jnp.asarray([[9, 8, 0, 0, 7, 0, 0, 0, 6, 6, 0, 0, 5, 5, 5, 5]],
                         jnp.float32)
    # groups of 4: scores 17, 7, 12, 10 -> groups 0 and 2 stay; the 7 of
    # group 1 is larger than anything in group 2 and is still left out
    got = sorted(np.asarray(group_limited_topk(choice, 4, 2, 4))[0])
    assert got == [0, 1, 8, 9]


# ------------------------------------------- the held experts' partial sum
@pytest.mark.parametrize("real_rows", [None, 5])
def test_held_experts_equal_a_loop_over_them(real_rows):
    N, E, M, X, k = 13, 32, 16, 8, 3
    first, held = 2, 4
    h = _rnd(0, (N, E))
    experts = {"gate_up": _rnd(1, (held, E, 2 * M), 0.2),
               "down": _rnd(2, (held, M, E), 0.2)}
    rng = np.random.default_rng(0)
    chosen = np.stack([rng.permutation(X)[:k] for _ in range(N)])
    weights = rng.random((N, k)).astype(np.float32)
    real = np.ones(N, bool)
    if real_rows is not None:
        real[real_rows:] = False
    out, counts = held_expert_mlp(h, jnp.asarray(chosen), jnp.asarray(weights),
                                  experts, first, jnp.asarray(real))
    want = np.zeros((N, E), np.float32)
    load = np.zeros(held, int)
    for n in range(N):
        for e, w in zip(chosen[n], weights[n]):
            if first <= e < first + held and real[n]:
                gu = np.asarray(h[n]) @ np.asarray(experts["gate_up"][e - first])
                act = gu[:M] / (1 + np.exp(-gu[:M])) * gu[M:]
                want[n] += w * (act @ np.asarray(experts["down"][e - first]))
                load[e - first] += 1
    np.testing.assert_allclose(np.asarray(out), want, atol=2e-5)
    assert list(np.asarray(counts)) == [load.sum(),
                                        real.sum() * k - load.sum(),
                                        load.max(), (load > 0).sum()]


# ----------------------------- (b) absorbed over the paged pool = expanded
def _paged_latents(lens, BS, W, lora, rope, seed=0):
    """A pool whose rows hold each slot's past latents, and the tables."""
    N = 1 + sum(-(-int(n) // BS) for n in lens)
    pool = np.zeros((2 * N, BS, W), np.float32)         # layer 1 of 2
    bt = np.zeros((len(lens), 1 + max(-(-int(n) // BS) for n in lens)),
                  np.int32)
    rows = [np.asarray(_rnd(seed + b, (int(n), lora + rope)))
            for b, n in enumerate(lens)]
    free = iter(np.random.default_rng(seed).permutation(np.arange(1, N)))
    for b, past in enumerate(rows):
        for i in range(-(-len(past) // BS)):
            bt[b, i] = next(free)
            part = past[i * BS:(i + 1) * BS]
            pool[N + bt[b, i], :len(part), :lora + rope] = part
    return jnp.asarray(pool), jnp.asarray(bt), rows, N


@pytest.mark.parametrize("walk", ["jnp", "kernel"])
def test_absorbed_decode_equals_expanded_attention(walk):
    """Scores against ``[c_kv ‖ k_pe]`` with ``q_nope @ W_UK^T`` for the
    query, the weighted sum over ``c_kv`` and ``W_UV`` after it, equal
    per-head keys and values expanded from the latents."""
    H, nope, rope, v, lora, BS, W = 4, 16, 8, 16, 32, 8, 128
    lens = np.array([0, 3, 8, 21, 40], np.int32)
    pool, bt, past, N = _paged_latents(lens, BS, W, lora, rope)
    B = len(lens)
    q_nope, q_pe = _rnd(50, (B, H, nope)), _rnd(51, (B, H, rope))
    cur = _rnd(52, (B, lora + rope))
    w_uk, w_uv = _rnd(53, (lora, H, nope), 0.3), _rnd(54, (lora, H, v), 0.3)
    scale = 0.2
    q = jnp.concatenate([jnp.einsum("bhd,chd->bhc", q_nope, w_uk), q_pe], -1)
    if walk == "kernel":
        o_lat = pa._decode_kernel_call(q, cur, None, N, pool, None, bt,
                                       jnp.asarray(lens), scale, group=2,
                                       interpret=True, v_width=lora)
    else:
        o_lat = pa.paged_decode_attention(q, cur, None, N, pool, None, bt,
                                          jnp.asarray(lens), sm_scale=scale,
                                          v_width=lora)
    got = np.asarray(jnp.einsum("bhc,chd->bhd", o_lat, w_uv))
    for b in range(B):
        rows = np.concatenate([past[b], np.asarray(cur[b])[None]])
        c_kv, k_pe = rows[:, :lora], rows[:, lora:]
        k = np.einsum("tc,chd->thd", c_kv, np.asarray(w_uk))
        val = np.einsum("tc,chd->thd", c_kv, np.asarray(w_uv))
        s = (np.einsum("hd,thd->ht", np.asarray(q_nope[b]), k)
             + np.asarray(q_pe[b]) @ k_pe.T) * scale
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(got[b], np.einsum("ht,thd->hd", p, val),
                                   atol=2e-5)


def test_the_chunk_walk_over_latents_equals_expanded_attention():
    """A prefill chunk at one slot without its batch dimension, and a
    batch of chunks: causal inside the chunk, the past from the pool, the
    trips several blocks wide."""
    H, nope, rope, v, lora, BS, W, C = 4, 16, 8, 16, 32, 8, 128, 5
    lens = np.array([0, 70, 133], np.int32)
    pool, bt, past, N = _paged_latents(lens, BS, W, lora, rope, seed=7)
    B = len(lens)
    q = _rnd(60, (B, H, C, lora + rope), 0.5)
    cur = _rnd(61, (B, C, lora + rope))
    kw = dict(sm_scale=0.25, v_width=lora)
    got = pa.paged_chunk_attention(q, cur, None, N, pool, None, bt,
                                   jnp.asarray(lens), **kw)
    lone = pa.paged_chunk_attention(q[2], cur[2], None, N, pool, None, bt[2],
                                    jnp.asarray(lens[2]), **kw)
    np.testing.assert_allclose(np.asarray(lone), np.asarray(got[2]),
                               atol=1e-5)
    for b in range(B):
        rows = np.concatenate([past[b], np.asarray(cur[b])])
        s = np.einsum("hcd,td->hct", np.asarray(q[b]), rows) * 0.25
        seen = np.arange(len(rows))[None] <= lens[b] + np.arange(C)[:, None]
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(got[b]), p @ rows[:, :lora],
                                   atol=2e-5)


# ----------------------------------------------------- the pool's layout
def test_a_latent_cache_is_one_pool_and_everything_follows_the_set():
    cache = PagedKVCache(n_layer=3, n_head=4, head_dim=24, block_size=8,
                         num_blocks=5, dtype=jnp.float32, latent_width=40)
    assert cache.row_width == 128
    assert {n: s for n, (s, _) in cache._pool_shapes().items()} == {
        "kv": (15, 8, 128)}
    assert cache.pool_bytes() == 15 * 8 * 128 * 4
    pools = cache.init_pools()
    rows = _rnd(0, (3, 2, 40))
    pools = cache.write_layers(pools, {"kv": rows}, jnp.asarray([2, 4]),
                               jnp.asarray([1, 7]))
    for layer in range(3):
        np.testing.assert_array_equal(pools["kv"][layer * 5 + 2, 1, :40],
                                      rows[layer, 0])
        np.testing.assert_array_equal(pools["kv"][layer * 5 + 4, 7, :40],
                                      rows[layer, 1])
    assert float(jnp.abs(pools["kv"][..., 40:]).max()) == 0.0
    assert float(jnp.abs(pools["kv"]).sum()) == pytest.approx(
        float(jnp.abs(rows).sum()), rel=1e-6)
    salt_of = {}
    for name, kw in (("latent", dict(latent_width=40)), ("heads", {})):
        c = PagedKVCache(n_layer=1, n_head=4, head_dim=24, block_size=8,
                         num_blocks=5, dtype=jnp.float32, **kw)
        salt_of[name] = c.attach_prefix_cache().root_digest
    assert salt_of["latent"] != salt_of["heads"]


# ------------------------------------------------------------ the server
def _serve(cfg, serving, dtype=jnp.float32, **engine_kw):
    model = MLAMoEForCausalLM(cfg)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(model, params=params, dtype=dtype,
                                          **engine_kw)
    return deepspeed_tpu.init_serving(engine=engine,
                                      config={"serving": serving})


def test_one_decode_and_one_prefill_program_and_the_counters_land():
    from deepspeed_tpu.telemetry import metrics
    registry = metrics.get_registry()
    names = ("serving_moe_pairs_held_total", "serving_moe_pairs_absent_total",
             "serving_moe_expert_load_max_total",
             "serving_moe_experts_touched_total")
    before = {n: registry.counter(n).value for n in names}
    cfg = tiny_config()
    srv = _serve(cfg, {"max_batch": 3, "block_size": 8, "prefill_chunk": 6,
                       "max_model_len": 128})
    rng = np.random.default_rng(0)
    lens = (5, 19, 33, 7, 12)
    for n in lens:
        srv.submit(rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                   max_new_tokens=9)
    outs = list(srv.serve_forever())
    assert sorted(len(o.tokens) for o in outs) == [9] * 5
    stats = srv.compile_stats()
    assert stats["decode_signatures"] == 1 and stats["retraces"] == 0
    assert stats["prefill_signatures"] == 1
    held, absent, most, touched = (registry.counter(n).value - before[n]
                                   for n in names)
    # every real token of every expert layer makes k choices: the prompts
    # less their last token through prefill, then 9 decode inputs a request
    tokens = sum(n - 1 for n in lens) + 9 * len(lens)
    expert_layers = cfg.num_hidden_layers - cfg.first_k_dense_replace
    assert held + absent == tokens * expert_layers * cfg.num_experts_per_tok
    assert 0 < held < absent and 0 < most <= held
    # the held experts with a pair, which the expert roofline reads
    assert 0 < touched <= held
    assert srv.cache.pool_bytes() == (3 * srv.cache.num_blocks * 8 * 128 * 4)
    srv.close()


def _gpt2(**changes):
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=64, n_positions=32, n_embd=16, n_layer=2,
                     n_head=2)
    params = GPT2LMHeadModel(cfg).init(
        jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 4), jnp.int32)})["params"]
    import dataclasses
    return GPT2LMHeadModel(dataclasses.replace(cfg, **changes)), params


@pytest.mark.parametrize("what, match", [
    ("speculation", "speculative decoding over a latent cache"),
    ("int8 weights", "int8 weights are not served for a latent"),
    ("int8 latent pool", "int8 latent pools"),
    ("tensor parallel", "tensor-parallel serving"),
    ("gpt2 rotary", "rotary positions in a GPT-2 block"),
    ("gpt2 pipeline", "pipeline-parallel serving"),
    ("gpt2 ring attention", "sequence-parallel and block-sparse attention"),
    ("no such model", "GPT2Config-like or a latent-attention"),
])
def test_what_is_not_served_is_refused_at_construction_by_name(what, match):
    """Never mid-step: the error comes out of ``init_serving`` (or the
    cache's constructor) and names the mechanism."""
    serving = {"max_batch": 2, "block_size": 8}
    with pytest.raises(NotImplementedError, match=match):
        if what == "speculation":
            _serve(tiny_config(), {**serving, "speculative": {
                "enabled": True, "k": 2, "draft_layers": 1}})
        elif what == "int8 weights":
            srv = _serve(tiny_config(), serving)
            srv.engine.quant_scales = {}
            type(srv)(srv.engine, config={"serving": serving})
        elif what == "int8 latent pool":
            PagedKVCache(n_layer=1, n_head=4, head_dim=24, block_size=8,
                         num_blocks=4, int8_kv=True, latent_width=40)
        elif what == "tensor parallel":
            srv = _serve(tiny_config(), serving)
            srv.engine.mp_world_size = 2
            type(srv)(srv.engine, config={"serving": serving})
        elif what == "no such model":
            class Bare:
                config = object()
            engine = _serve(tiny_config(), serving).engine
            engine.module = Bare()
            deepspeed_tpu.init_serving(engine=engine,
                                       config={"serving": serving})
        else:
            model, params = _gpt2(**{
                "gpt2 rotary": dict(position_embedding="rope"),
                "gpt2 pipeline": dict(pp_stages=2),
                "gpt2 ring attention": dict(attention_mode="ring:data"),
            }[what])
            engine = deepspeed_tpu.init_inference(model, params=params,
                                                  dtype=jnp.float32)
            engine.module = model
            deepspeed_tpu.init_serving(engine=engine,
                                       config={"serving": serving})
