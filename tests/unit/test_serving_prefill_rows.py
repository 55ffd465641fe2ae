"""A step's prefill chunks share a dispatch: the prefill program runs ``R``
slots' chunks, one a row, and the server packs a step's chunks into
``ceil(n / R)`` calls, padding only the last.

For each of the three block kinds at a test size on the CPU, ``R`` rows
serve the same greedy tokens and leave the same pools as one chunk a
dispatch (the same server with ``prefill.rows`` set to 1), through pad
rows, a re-prefill after a preemption and a prefix cache's tail chunk; a
pad row moves no slot's per-slot state and adds nothing to the expert
counts; ``R`` follows the chunk width; every chunk keeps its
``serving_prefill`` span, inside its dispatch's ``serving_prefill_dispatch``.
Counts and results only: a CPU run yields no time worth asserting."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.serving.prefill import RIDGE_POSITIONS, prefill_rows
from deepspeed_tpu.serving.server import ServingEngine
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.metrics import MetricsRegistry

VOCAB = 256


def _gpt2():
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=128, n_embd=32,
                     n_layer=2, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(1),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return model, params


def _latent():
    from deepspeed_tpu.models.mla_moe import (MLAMoEConfig,
                                              MLAMoEForCausalLM, init_params)
    cfg = MLAMoEConfig(
        vocab_size=VOCAB, hidden_size=64, num_hidden_layers=2,
        first_k_dense_replace=1, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=16, experts_held=(4, 8),
        num_experts_per_tok=4, n_group=4, topk_group=2,
        routed_scaling_factor=2.5, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=16, max_position_embeddings=128, rope_factor=40.0,
        rope_original_max_position=64, rope_mscale=1.0,
        rope_mscale_all_dim=1.0)
    return MLAMoEForCausalLM(cfg), init_params(cfg, jax.random.PRNGKey(2))


def _state():
    from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                                 SSMHybridForCausalLM,
                                                 init_params)
    cfg = SSMHybridConfig(
        vocab_size=VOCAB, hidden_size=64, intermediate_size=128,
        layer_types=("mamba", "attention", "mamba"), num_attention_heads=4,
        num_key_value_heads=2, mamba_n_heads=4, mamba_d_head=16,
        mamba_d_state=16, max_position_embeddings=128,
        attention_multiplier=0.0625, residual_multiplier=0.22,
        logits_scaling=8.0)
    return SSMHybridForCausalLM(cfg), init_params(cfg, jax.random.PRNGKey(3))


KINDS = {"gpt2": _gpt2, "latent": _latent, "state": _state}


@pytest.fixture(scope="module", params=list(KINDS))
def engine(request):
    model, params = KINDS[request.param]()
    return request.param, deepspeed_tpu.init_inference(
        model, params=params, dtype=jnp.float32)


def _server(engine, rows=None, **serving):
    """A server over ``engine``; ``rows`` set by the test where it wants
    one chunk a dispatch."""
    serving = {"max_batch": 4, "block_size": 8, "prefill_chunk": 8,
               "max_model_len": 128, **serving}
    srv = ServingEngine(engine, config=serving, registry=MetricsRegistry())
    if rows is not None:
        srv.prefill.rows = rows
    return srv


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32) for n in lengths]


def _serve(srv, prompts, new=6):
    """Serve ``prompts`` whole; the tokens by request, the chunks and the
    dispatches counted, and the pools as the last step left them."""
    rids = [srv.submit(p, max_new_tokens=new) for p in prompts]
    outs = {o.req_id: o.tokens for o in srv.serve_forever()}
    counted = {n: srv.registry.counter(n).value for n in (
        "serving_prefill_chunks_total", "serving_prefill_dispatches_total",
        "serving_recompute_tokens_total")}
    pools = {n: np.asarray(p) for n, p in srv.pools.items()}
    srv.close()
    return [outs[r] for r in rids], counted, pools


def _real_blocks(srv, name, pool):
    """A pool less its null block's rows (pad positions and pad rows write
    there, so it holds whatever the last of them wrote)."""
    if srv.cache.pool_kinds()[name] != "paged":
        return pool
    return pool[np.arange(len(pool)) % srv.cache.num_blocks != 0]


def _same_pools(srv, got, want):
    assert set(got) == set(want)
    for name in want:
        np.testing.assert_allclose(_real_blocks(srv, name, got[name]),
                                   _real_blocks(srv, name, want[name]),
                                   rtol=1e-6, atol=1e-6, err_msg=name)


# -------------------------------------------------------------- the R rule
@pytest.mark.parametrize("chunk, slots, rows", [
    (128, 384, 2), (128, 96, 2), (256, 64, 1), (512, 96, 1),
    (8, 4, 4), (8, 64, 32), (100, 384, 3), (128, 1, 1), (6, 3, 3)])
def test_rows_hold_the_ridges_positions_and_no_more_than_the_slots(
        chunk, slots, rows):
    assert prefill_rows(chunk, slots) == rows
    assert RIDGE_POSITIONS == 256


def test_the_engine_takes_its_rows_from_the_chunk_width(engine):
    _, eng = engine
    for chunk, slots, rows in [(8, 4, 4), (8, 2, 2), (128, 4, 2),
                               (256, 4, 1)]:
        srv = _server(eng, prefill_chunk=chunk, max_batch=slots)
        assert srv.prefill.rows == rows
        srv.close()


# ------------------------------------------- R rows = one chunk a dispatch
def test_rows_serve_what_one_chunk_a_dispatch_serves(engine):
    """Prompts of 3-40 tokens through 4 slots at chunks of 8: steps of one
    to four chunks, so dispatches with and without pad rows; the same
    tokens, the same pools, one prefill program."""
    _, eng = engine
    prompts = _prompts(0, (33, 5, 17, 40, 9, 3, 26))
    srv = _server(eng)
    toks, counted, pools = _serve(srv, prompts)
    one_toks, one_counted, one_pools = _serve(_server(eng, rows=1), prompts)
    assert toks == one_toks
    _same_pools(srv, pools, one_pools)
    chunks = counted["serving_prefill_chunks_total"]
    assert chunks == one_counted["serving_prefill_chunks_total"]
    assert one_counted["serving_prefill_dispatches_total"] == chunks
    dispatches = counted["serving_prefill_dispatches_total"]
    # fewer calls than chunks, and some with pad rows
    assert dispatches < chunks < 4 * dispatches
    stats = srv.compile_stats()
    assert stats["prefill_signatures"] == 1 and stats["retraces"] == 0


def test_a_re_prefill_after_a_preemption_goes_through_the_rows(engine):
    """A pool too small for the slots' requests evicts one, whose KV (and
    state) is prefilled again on re-admission: the same tokens with rows
    as with one chunk a dispatch."""
    _, eng = engine
    prompts = _prompts(5, (15, 15, 15))
    small = dict(max_batch=3, num_blocks=9)      # 8 usable blocks of 8
    toks, counted, _ = _serve(_server(eng, **small), prompts, new=20)
    one_toks, one_counted, _ = _serve(_server(eng, rows=1, **small),
                                      prompts, new=20)
    assert counted["serving_recompute_tokens_total"] > 0, \
        "the scenario must re-prefill"
    for name in ("serving_prefill_chunks_total",
                 "serving_recompute_tokens_total"):
        assert counted[name] == one_counted[name]
    assert toks == one_toks


@pytest.mark.parametrize("kind", ["gpt2", "latent"])   # none over state
def test_a_prefix_caches_tail_chunk_goes_through_the_rows(kind):
    """Prompts that share 16 tokens: a later admission matches them and
    prefills its tail only, beside other slots' chunks."""
    model, params = KINDS[kind]()
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    rng = np.random.default_rng(9)
    shared = rng.integers(0, VOCAB, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared, t]) for t in
               _prompts(10, (3, 11, 20, 6, 14))]
    cache = {"prefix_cache": {"enabled": True}, "max_batch": 2}
    srv = _server(eng, **cache)
    toks, counted, _ = _serve(srv, prompts)
    assert srv.cache.prefix_cache.hits > 0
    one = _server(eng, rows=1, **cache)
    one_toks, _, _ = _serve(one, prompts)
    assert one.cache.prefix_cache.hits == srv.cache.prefix_cache.hits
    assert toks == one_toks
    assert counted["serving_prefill_dispatches_total"] < \
        counted["serving_prefill_chunks_total"]


# ------------------------------------------------------------ the pad row
def _chunk_args(srv, rows):
    """The program's arguments for ``rows`` = [(slot, start, tokens)] and
    pad rows after them, as :meth:`ChunkedPrefill.dispatch` lays them."""
    R, C, MB = len(rows) + 2, srv.prefill.chunk_size, srv.max_blocks_per_seq
    bt = np.zeros((R, MB), np.int32)
    tok = np.zeros((R, C), np.int32)
    start, n_valid, slot = (np.zeros((R,), np.int32) for _ in range(3))
    for i, (s, st, t) in enumerate(rows):
        bt[i] = 1 + s * MB + np.arange(MB)
        tok[i, :len(t)], start[i], n_valid[i], slot[i] = t, st, len(t), s
    # one pad row names a live slot, the other the real row's own slot
    slot[-2], slot[-1] = 0, rows[0][0]
    return tuple(map(jnp.asarray, (bt, tok, start, n_valid, slot)))


def test_a_pad_row_moves_no_slots_state_and_no_kv():
    """Every slot's state made non-zero by a first chunk; then slot 1's
    second chunk beside two pad rows (one naming slot 0, one slot 1
    itself) leaves slot 1 as the lone chunk does and every other slot,
    and every other block, bit for bit as it was."""
    model, params = _state()
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    srv = _server(eng, max_batch=3)
    run = srv.runner._prefill_impl
    prompts = _prompts(11, (8, 8, 8, 6))
    MB = srv.max_blocks_per_seq
    pools = srv.pools

    def table(s):
        return jnp.asarray(1 + s * MB + np.arange(MB, dtype=np.int32))
    for s in range(3):                  # a state in every slot
        pools, _ = run(eng.params, {}, pools, table(s),
                       jnp.asarray(prompts[s]), jnp.int32(0), jnp.int32(8),
                       jnp.int32(s))
    before = {n: np.asarray(p) for n, p in pools.items()}
    lone, _ = run(eng.params, {}, pools, table(1),
                  jnp.asarray(np.pad(prompts[3], (0, 2))), jnp.int32(8),
                  jnp.int32(6), jnp.int32(1))
    rows, _ = run(eng.params, {}, pools,
                  *_chunk_args(srv, [(1, 8, prompts[3])]))
    for name in ("ssm", "conv"):
        got, want = np.asarray(rows[name]), np.asarray(lone[name])
        np.testing.assert_allclose(got[:, 1], want[:, 1], rtol=1e-6,
                                   atol=1e-6)
        assert np.abs(got[:, 1] - before[name][:, 1]).max() > 0
        for s in (0, 2):
            np.testing.assert_array_equal(got[:, s], before[name][:, s])
    # the real row's blocks hold its tokens; the null block aside, no
    # other block moved
    for name in ("k", "v"):
        got, was = np.asarray(rows[name]), before[name]
        np.testing.assert_allclose(
            _real_blocks(srv, name, got),
            _real_blocks(srv, name, np.asarray(lone[name])), rtol=1e-6,
            atol=1e-6)
        moved = np.flatnonzero(np.abs(got - was).reshape(len(got), -1)
                               .max(axis=1) > 0) % srv.cache.num_blocks
        assert set(moved) <= {0, 1 + MB + 1}, moved
    srv.close()


def test_pad_rows_add_nothing_to_the_expert_counts():
    """Two chunks beside two pad rows count what the two chunks count
    alone, and a call of one chunk what that chunk does."""
    model, params = _latent()
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    srv = _server(eng)
    run = srv.runner._prefill_impl
    chunks = [(2, 0, t) for t in _prompts(12, (5, 8))]
    chunks[1] = (3, 0, chunks[1][2])
    MB = srv.max_blocks_per_seq
    alone = []
    for s, start, tokens in chunks:
        _, counts = run(eng.params, {}, srv.pools,
                        jnp.asarray(1 + s * MB + np.arange(MB,
                                                           dtype=np.int32)),
                        jnp.asarray(np.pad(tokens, (0, 8 - len(tokens)))),
                        jnp.int32(start), jnp.int32(len(tokens)),
                        jnp.int32(s))
        alone.append(np.asarray(counts))
    _, both = run(eng.params, {}, srv.pools, *_chunk_args(srv, chunks))
    _, first = run(eng.params, {}, srv.pools, *_chunk_args(srv, chunks[:1]))
    held, absent = np.asarray(both)[:2]
    assert (held, absent) == tuple(alone[0][:2] + alone[1][:2])
    np.testing.assert_array_equal(np.asarray(first), alone[0])
    srv.close()


# -------------------------------------------------------------- the spans
@contextlib.contextmanager
def _live_tracer(tmp_path):
    mine = tracer_mod.Tracer()
    old = tracer_mod.set_tracer(mine)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        yield mine
    finally:
        jax.profiler.stop_trace()
        tracer_mod.set_tracer(old)


def test_each_chunk_keeps_its_span_inside_its_dispatchs(tmp_path):
    model, params = _gpt2()
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    srv = _server(eng, max_batch=3)             # rows = 3
    for p in _prompts(13, (20, 9, 30, 4)):
        srv.submit(p, max_new_tokens=3)
    chunks = srv.registry.counter("serving_prefill_chunks_total")
    dispatches = srv.registry.counter("serving_prefill_dispatches_total")
    with _live_tracer(tmp_path) as tracer:
        list(srv.serve_forever())
    events = [e for e in tracer.events() if e.get("ph") == "X"]
    outer = [e for e in events if e["name"] == "serving_prefill_dispatch"]
    inner = [e for e in events if e["name"] == "serving_prefill"]
    assert len(outer) == dispatches.value and len(inner) == chunks.value
    assert len(outer) < len(inner)

    def within(e, o):
        return o["ts"] <= e["ts"] and \
            e["ts"] + e["dur"] <= o["ts"] + o["dur"] + 1
    for o in outer:
        mine = [e for e in inner if within(e, o)]
        assert o["args"]["rows"] == 3
        assert o["args"]["chunks"] == len(mine) >= 1
        for e in mine:
            assert {"req", "start", "tokens", "recompute"} <= set(e["args"])
            assert 1 <= e["args"]["tokens"] <= 8
        # a dispatch holds each of its slots once
        assert len({e["args"]["req"] for e in mine}) == len(mine)
    assert sum(o["args"]["chunks"] for o in outer) == len(inner)
    srv.close()
