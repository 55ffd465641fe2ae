"""The state-space hybrid on the serving path, at a test size on the CPU:
the chunked scan against the token-by-token recurrence, the state and the
convolution's rows that a part-empty chunk leaves, the ``ssm_decode``
kernel (interpret mode) against its jnp form, the state's float32 over a
whole request against a bfloat16 control, a reused slot and a
recomputed request against a fresh server, grouped-query attention in the
decode kernel and the walk, and what is refused at construction. The
served logits against the plain reference are
tests/benchmark/test_benchmark_granite_4_0_h.py's."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                             SSMHybridForCausalLM,
                                             init_params)
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.ssm import decode as ssm_decode
from deepspeed_tpu.serving import paged_attention
from deepspeed_tpu.serving.runner import ServingNotSupported


def tiny_config(**changes):
    kw = dict(vocab_size=512, hidden_size=64, intermediate_size=128,
              layer_types=("mamba", "attention", "mamba", "mamba"),
              num_attention_heads=4, num_key_value_heads=2, mamba_n_heads=4,
              mamba_d_head=16, mamba_d_state=16, max_position_embeddings=512,
              attention_multiplier=0.0625, residual_multiplier=0.22,
              logits_scaling=8.0)
    return SSMHybridConfig(**{**kw, **changes})


def _rnd(seed, shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


def _recurrence(x, b, c, dt, a, s):
    """The state-space recurrence a token at a time (package docstring)."""
    ys = []
    for t in range(x.shape[0]):
        s = jnp.exp(dt[t] * a)[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * b[t][None, None, :]
        ys.append(jnp.einsum("hpn,n->hp", s, c[t],
                             precision=jax.lax.Precision.HIGHEST))
    return jnp.stack(ys), s


# ----------------------------------------------- (b) the scan = the recurrence
@pytest.mark.parametrize("start", ["zero", "carried"])
def test_the_chunked_scan_equals_the_recurrence(start):
    T, H, P, N = 13, 3, 8, 16
    x, b, c = _rnd(1, (T, H, P)), _rnd(2, (T, N)), _rnd(3, (T, N))
    dt = jax.nn.softplus(_rnd(4, (T, H)) - 2.0)
    a = -jnp.exp(_rnd(5, (H,)))
    s0 = (jnp.zeros((H, P, N)) if start == "zero"
          else _rnd(6, (H, P, N)))
    y, s_end = ssm.chunk_scan(x, b[:, None], c[:, None], dt, a, s0)
    want_y, want_s = _recurrence(x, b, c, dt, a, s0)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(s_end, want_s, rtol=1e-5, atol=1e-5)
    # the packed layout round-trips
    np.testing.assert_array_equal(
        ssm.to_heads(ssm.from_heads(s_end), H, P), s_end)


# ------------------------------------------------------------- the server
def _serve(cfg=None, dtype=jnp.float32, params=None, **serving):
    cfg = cfg or tiny_config()
    params = params if params is not None else init_params(
        cfg, jax.random.PRNGKey(0))
    engine = deepspeed_tpu.init_inference(SSMHybridForCausalLM(cfg),
                                          params=params, dtype=dtype)
    serving = {"max_batch": 2, "block_size": 8, "prefill_chunk": 7,
               "max_model_len": 128, **serving}
    return deepspeed_tpu.init_serving(engine=engine,
                                      config={"serving": serving})


def _slot_state(pools, slot):
    return {n: np.asarray(pools[n][:, slot]) for n in ("ssm", "conv")}


# ---------------------- (c) a part-empty last chunk leaves the state exactly
@pytest.mark.parametrize("n_real", [1, 2])
def test_a_last_chunk_of_few_tokens_leaves_the_state_of_its_last_token(
        n_real):
    """After a first chunk of 7, a chunk of 7 that holds ``n_real`` tokens
    and a pad tail leaves slot 1's state and convolution rows as a chunk of
    exactly those tokens does; slot 0 is not touched."""
    srv = _serve()
    runner = srv.runner
    prompt = np.random.default_rng(2).integers(0, 512, 7 + n_real)
    bt = np.arange(1, 1 + srv.max_blocks_per_seq, dtype=np.int32)

    def chunk(pools, start, tokens, width):
        padded = np.zeros((width,), np.int32)
        padded[:len(tokens)] = tokens
        pools, _ = runner._prefill_impl(
            srv.engine.params, {}, pools, jnp.asarray(bt),
            jnp.asarray(padded), jnp.int32(start), jnp.int32(len(tokens)),
            jnp.int32(1))
        return pools

    pools = chunk(srv.pools, 0, prompt[:7], 7)
    padded = chunk(pools, 7, prompt[7:], 7)
    exact = chunk(pools, 7, prompt[7:], n_real)
    got, want = _slot_state(padded, 1), _slot_state(exact, 1)
    for name in got:
        np.testing.assert_allclose(got[name], want[name], rtol=1e-6,
                                   atol=1e-7)
        assert np.abs(got[name] - _slot_state(pools, 1)[name]).max() > 0
        np.testing.assert_array_equal(_slot_state(padded, 0)[name], 0)
    srv.close()


# ------------------------ (d) the kernel (interpret) = its jnp form
@pytest.mark.parametrize("live", [(1, 1, 1), (1, 0, 1), (0, 0, 0)])
def test_the_decode_kernel_equals_jnp_and_keeps_frozen_slots(live):
    B, R, N, layers = 3, 2, 16, 2
    pool = _rnd(1, (layers, B, R, N, 128))
    b, c = _rnd(2, (B, N)), _rnd(3, (B, N))
    decay = jax.random.uniform(jax.random.PRNGKey(4), (B, R, 128))
    u = _rnd(5, (B, R, 128))
    live = jnp.asarray(live, bool)
    fresh = jnp.asarray([False, False, True]) & live
    y, out = ssm_decode._decode_call(pool, 1, b[:, None], c[:, None], decay,
                                     u, live, fresh, interpret=True)
    want_y, want_s = ssm_decode.step(pool[1], b[:, None], c[:, None], decay,
                                     u, live, fresh)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(out[1], want_s, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(out[0], pool[0])
    for s in range(B):
        if not live[s]:
            np.testing.assert_array_equal(out[1, s], pool[1, s])


# ------------- the state keeps float32 over the longest request it serves
def _dynamics(T, H, P, N, seed=0):
    """A request's inputs to one Mamba-2 layer at granite-4.0-h-micro's
    widths: ``dt = softplus(raw + dt_bias)`` with ``raw ~ N(0, 0.9)`` (an
    RMS-normed row through 2,048 rows of N(0, 0.02)), ``dt_bias`` from a
    step log-uniform on [0.001, 0.1], ``A = -U[1, 16]``."""
    rng = np.random.default_rng(seed)
    start = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), H))
    dt_bias = start + np.log(-np.expm1(-start))
    dt = np.logaddexp(0.0, 0.9 * rng.standard_normal((T, H)) + dt_bias)
    return (0.5 * rng.standard_normal((T, H, P)),
            0.5 * rng.standard_normal((T, N)),
            0.5 * rng.standard_normal((T, N)), dt, -rng.uniform(1, 16, H))


@pytest.mark.parametrize("state", ["served", "bfloat16"])
@pytest.mark.parametrize("path", ["jnp", "kernel"])
def test_the_decode_state_keeps_float32_over_the_longest_request(
        path, state, monkeypatch):
    """896 tokens (the benchmark's longest request, 640 + 256) through a
    slot of one layer at granite-4.0-h-micro's widths: the outputs stay
    within 1e-5 (rms, relative) of the float64 recurrence with the pool
    dtype the server builds (float32: about 1.5e-7). The same pool in
    bfloat16, the control, reads about 3e-3 at every position: that is what
    the bound tells apart, and what the benchmark's greedy token gaps over
    this traffic do not (PERF.md)."""
    from deepspeed_tpu.serving.runner import cache_rows
    if path == "kernel":
        monkeypatch.setattr(ssm_decode, "decode_kernel_runs", lambda: True)
        monkeypatch.setattr(ssm_decode, "_decode_call", functools.partial(
            ssm_decode._decode_call, interpret=True))
    T, H, P, N = 896, 64, 64, 128
    x, b, c, dt, a = _dynamics(T, H, P, N)
    s, want = np.zeros((H, P, N)), []
    for t in range(T):
        s = np.exp(dt[t] * a)[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * b[t]
        want.append(s @ c[t])
    dtype = (cache_rows(tiny_config())["slot_state"]["ssm"][1]
             if state == "served" else jnp.bfloat16)
    pool = jnp.zeros((1, 1, ssm.packed_rows(H, P), N, 128), dtype)
    update, got = jax.jit(ssm.decode_update), []

    def f32(v):
        return jnp.asarray(v[None], jnp.float32)

    for t in range(T):
        y, pool = update(pool, 0, f32(x[t]), f32(b[t][None]),
                         f32(c[t][None]),
                         f32(dt[t]), jnp.asarray(a, jnp.float32),
                         jnp.ones((1,), bool), jnp.asarray([t == 0]))
        got.append(np.asarray(y[0]))
    want = np.stack(want)
    err = np.sqrt(np.mean((np.stack(got) - want) ** 2) / np.mean(want ** 2))
    if state == "served":
        assert err < 1e-5
    else:
        assert err > 1e-3


# ---------------- (e) a reused slot and a recomputed request = a fresh server
def _outputs(srv, prompts, n_new):
    ids = [srv.submit(p, max_new_tokens=n_new) for p in prompts]
    outs = {o.req_id: o.tokens for o in srv.serve_forever()}
    return [outs[i] for i in ids]


def test_a_reused_slot_serves_as_a_fresh_server_does():
    rng = np.random.default_rng(5)
    first, second = rng.integers(0, 512, 23), rng.integers(0, 512, 17)
    reused = _serve(max_batch=1)
    _outputs(reused, [first], 12)
    got = _outputs(reused, [second], 12)
    fresh = _serve(max_batch=1)
    want = _outputs(fresh, [second], 12)
    assert got == want
    for name, rows in _slot_state(reused.pools, 0).items():
        np.testing.assert_array_equal(rows,
                                      _slot_state(fresh.pools, 0)[name])


def test_a_preempted_request_is_recomputed_from_a_zero_state():
    """Two slots over a pool too small for both requests to finish: one is
    preempted and prefilled again from position 0; every request's tokens
    are a fresh server's for it alone."""
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, 512, n) for n in (20, 26)]
    tight = _serve(num_blocks=9)
    got = _outputs(tight, prompts, 24)
    assert tight.scheduler.preemptions_total > 0
    for prompt, tokens in zip(prompts, got):
        assert tokens == _outputs(_serve(max_batch=1), [prompt], 24)[0]


# ------------------ (f) grouped queries in the decode kernel and the walk
def _paged(B, K, D, BS, lens, seed=0):
    rng = np.random.default_rng(seed)
    W = -(-K * D // 128) * 128
    n = 1 + sum(-(-length // BS) for length in lens)
    k_pool = jnp.asarray(rng.standard_normal((n, BS, W)), jnp.float32)
    v_pool = jnp.asarray(rng.standard_normal((n, BS, W)), jnp.float32)
    bt = np.zeros((B, max(-(-length // BS) for length in lens) + 1),
                  np.int32)
    nxt = 1
    for b, length in enumerate(lens):
        for i in range(-(-length // BS)):
            bt[b, i] = nxt
            nxt += 1
    return k_pool, v_pool, jnp.asarray(bt)


@pytest.mark.parametrize("heads, kv_heads", [(8, 2), (32, 8), (4, 4)],
                         ids=["groups-of-4", "granite-heads", "mha"])
def test_grouped_queries_equal_dense_attention_with_repeated_kv(heads,
                                                               kv_heads):
    B, D, BS, lens = 3, 16, 8, [5, 0, 19]
    k_pool, v_pool, bt = _paged(B, kv_heads, D, BS, lens)
    q = _rnd(7, (B, heads, D))
    k_cur, v_cur = _rnd(8, (B, kv_heads, D)), _rnd(9, (B, kv_heads, D))
    past = jnp.asarray(lens, jnp.int32)
    kernel = paged_attention._decode_kernel_call(
        q, k_cur, v_cur, jnp.int32(0), k_pool, v_pool, bt, past,
        D ** -0.5, interpret=True)
    walk = paged_attention.paged_chunk_attention(
        q[:, :, None], k_cur[:, :, None], v_cur[:, :, None], 0, k_pool,
        v_pool, bt, past)[:, :, 0]
    group = heads // kv_heads
    for b, length in enumerate(lens):
        rows = [(bt[b, t // BS], t % BS) for t in range(length)]
        keys = jnp.stack([k_pool[r, o, :kv_heads * D].reshape(kv_heads, D)
                          for r, o in rows] + [k_cur[b]])
        values = jnp.stack([v_pool[r, o, :kv_heads * D].reshape(kv_heads, D)
                            for r, o in rows] + [v_cur[b]])
        keys = jnp.repeat(keys, group, axis=1)
        values = jnp.repeat(values, group, axis=1)
        probs = jax.nn.softmax(jnp.einsum("hd,thd->ht", q[b], keys)
                               * D ** -0.5, -1)
        want = jnp.einsum("ht,thd->hd", probs, values)
        np.testing.assert_allclose(kernel[b], want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(walk[b], want, rtol=1e-5, atol=1e-5)


# ---------------------------------- (g) what does not compose is refused
@pytest.mark.parametrize("what, match", [
    ("prefix cache", "prefix cache over per-slot state"),
    ("speculation", "speculative decoding over per-slot state"),
    ("int8 weights", "int8 weights are not served for a state-space"),
    ("grouped B and C", "grouped B and C"),
])
def test_what_does_not_compose_with_state_is_refused_by_name(what, match):
    with pytest.raises(ServingNotSupported, match=match):
        if what == "prefix cache":
            _serve(prefix_cache={"enabled": True})
        elif what == "speculation":
            _serve(speculative={"enabled": True, "k": 2, "draft_layers": 1})
        elif what == "int8 weights":
            srv = _serve()
            srv.engine.quant_scales = {}
            type(srv)(srv.engine, config={"serving": {"max_batch": 2}})
        else:
            cfg = tiny_config(mamba_n_groups=2)
            _serve(cfg=cfg, params=init_params(tiny_config(),
                                               jax.random.PRNGKey(0)))


def test_the_pools_follow_their_kind_and_the_counters_land():
    from deepspeed_tpu.telemetry import metrics
    registry = metrics.get_registry()
    resets = registry.counter("serving_state_resets_total")
    before = resets.value
    srv = _serve(max_batch=3)
    kinds = srv.cache.pool_kinds()
    assert kinds == {"k": "paged", "v": "paged", "ssm": "per_slot",
                     "conv": "per_slot"}
    assert srv.pools["k"].shape == (srv.cache.num_blocks, 8, 128)
    assert srv.pools["ssm"].shape == (3, 3, 1, 16, 128)
    assert srv.pools["ssm"].dtype == jnp.float32
    assert srv.pools["conv"].shape == (3, 3, 3 * (64 + 32))
    assert srv.cache.pool_bytes("per_slot") == 4 * 3 * 3 * 16 * 128 \
        + 4 * 3 * 3 * 3 * 96
    assert registry.gauge("serving_state_pool_bytes").value == \
        srv.cache.pool_bytes("per_slot")
    rng = np.random.default_rng(0)
    lens = (5, 19, 1, 7)
    outs = _outputs(srv, [rng.integers(0, 512, n) for n in lens], 6)
    assert [len(t) for t in outs] == [6] * 4
    stats = srv.compile_stats()
    assert stats["decode_signatures"] == 1 and stats["retraces"] == 0
    assert stats["prefill_signatures"] == 1
    # every request starts its state once: a chunk at 0, or (a one-token
    # prompt) its first decode row
    assert resets.value - before == len(lens)
    # the block copy leaves the per-slot pools alone
    kept = {name: np.asarray(srv.pools[name]) for name in ("ssm", "conv")}
    pools = srv.runner.copy_block(srv.pools, 1, 2)
    for name, rows in kept.items():
        np.testing.assert_array_equal(pools[name], rows)
    srv.pools = pools
    srv.close()
