"""The serving step runs one step ahead of the device (ISSUE 33).

``ServingEngine.step()`` call k schedules step k from counts, dispatches
it, and only then lands the tokens of step k-1. What that may not change
is the work: the same tokens, token for token, as the order that lands
every step's tokens before the next one is scheduled, which is what the
server did before and what a test gets by calling ``_land`` after every
``step()``. The cases cover what differs between the two orders: the
decode input that stays on the device, counts that advance at dispatch,
the slot held while a last token is in flight, the row spent past an EOS,
the landing before an eviction, the prefix index fed at landing, and
speculation, which lands its own step.

``submit()`` is host work only (the RNG lane is computed on the host and
pinned here against ``jax.random.PRNGKey``), and a step in steady state
reads exactly one device array back: the tokens of the step before.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.serving.sampling import make_rng_lane
from deepspeed_tpu.serving.scheduler import RequestState
from deepspeed_tpu.serving.server import ServingEngine
from deepspeed_tpu.telemetry.metrics import MetricsRegistry
from deepspeed_tpu.utils import groups

VOCAB = 256


@pytest.fixture(scope="module")
def engine():
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    return deepspeed_tpu.init_inference(model, params=params,
                                        dtype=jnp.float32)


def _server(engine, **config):
    config = {"max_batch": 3, "block_size": 8, "prefill_chunk": 6, **config}
    return ServingEngine(engine, config=config, registry=MetricsRegistry())


def _count(srv, name, **labels):
    return srv.registry.counter(name, labels=labels or None).value


def _serve_landing_first(srv):
    """The order the server had: every step's tokens land before the next
    step is scheduled."""
    outs = []
    for _ in range(2000):
        if not srv.scheduler.has_work():
            return outs
        srv.step()
        srv._land("drain")
        outs.extend(srv.collect())
    raise AssertionError("the landing-first loop did not drain")


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, (n,)).astype(np.int32) for n in lengths]


GREEDY = [dict(max_new_tokens=g) for g in (5, 3, 9, 5, 2, 7)]
SAMPLED = [dict(max_new_tokens=g, temperature=t, top_p=p, seed=s)
           for g, t, p, s in ((6, 0.8, 0.9, 1), (4, 0.0, 1.0, 0),
                              (9, 1.2, 1.0, 2 ** 40 + 5), (5, 0.7, 0.5, -1),
                              (3, 0.0, 1.0, 3), (8, 1.0, 0.95, 2 ** 31))]
LENGTHS = (1, 11, 30, 7, 19, 4)

# case -> (serving config, prompt lengths, submit kwargs of each request)
CASES = {
    "greedy": ({}, LENGTHS, GREEDY),
    "sampled": ({}, LENGTHS, SAMPLED),
    "greedy-decode_steps4": ({"decode_steps": 4}, LENGTHS, GREEDY),
    "sampled-decode_steps4": ({"decode_steps": 4}, LENGTHS, SAMPLED),
    # 6 usable blocks x 8 = 48 positions for two requests needing 35 each
    "eviction": ({"max_batch": 2, "num_blocks": 7, "prefill_chunk": 32},
                 (15, 15), [dict(max_new_tokens=20)] * 2),
    "eviction-decode_steps4": (
        {"max_batch": 2, "num_blocks": 7, "prefill_chunk": 32,
         "decode_steps": 4}, (15, 15), [dict(max_new_tokens=20)] * 2),
    "speculative": ({"speculative": {"enabled": True, "k": 3,
                                     "draft_layers": 1}}, LENGTHS, GREEDY),
}


@pytest.mark.parametrize("case", list(CASES))
def test_run_ahead_serves_the_tokens_of_landing_first(engine, case):
    config, lengths, kwargs = CASES[case]
    prompts = _prompts(7, lengths)
    ahead, first = _server(engine, **config), _server(engine, **config)
    evicted = []
    for srv in (ahead, first):
        preempt = srv.scheduler._preempt

        def checked(req, reason="capacity_growth", _preempt=preempt):
            # what is re-queued must hold every token whose KV was written
            assert req.in_flight == 0
            assert req.cached_len <= len(req.full_prompt) - 1
            if req.state is RequestState.RUNNING:
                assert req.cached_len == len(req.full_prompt) - 1
            evicted.append(req.req_id)
            return _preempt(req, reason)

        srv.scheduler._preempt = checked
        for p, kw in zip(prompts, kwargs):
            srv.submit(p, **kw)
    got = {o.req_id: o for o in ahead.serve_forever()}
    want = {o.req_id: o for o in _serve_landing_first(first)}
    assert sorted(got) == sorted(want) == list(range(len(prompts)))
    for rid, kw in enumerate(kwargs):
        assert got[rid].tokens == want[rid].tokens, (case, rid)
        assert got[rid].finish_reason == want[rid].finish_reason \
            == "max_tokens"
        assert len(got[rid].tokens) == kw["max_new_tokens"]
    for srv in (ahead, first):
        assert srv._in_flight is None
        assert srv.scheduler.num_active == 0
        srv.cache.allocator.check_consistency()
        assert srv.cache.allocator.num_allocated == 0
        assert _count(srv, "serving_decode_overrun_tokens_total") == 0
    assert _count(first, "serving_steps_ahead_total") == 0
    assert _count(first, "serving_slot_steps_awaiting_landing_total") == 0
    if case == "speculative":
        # ``accepted`` decides the next positions: each step lands its own
        assert _count(ahead, "serving_steps_ahead_total") == 0
        assert _count(ahead, "serving_steps_landed_first_total",
                      reason="speculation") \
            == _count(ahead, "serving_decode_steps_total") > 0
        assert ahead.compile_stats()["decode_signatures"] == 0
        return
    assert _count(ahead, "serving_steps_ahead_total") > 0
    # the price of the form: one slot-step a request, its last token in
    # flight (a request evicted in between pays it once all the same)
    assert _count(ahead, "serving_slot_steps_awaiting_landing_total") \
        == len(prompts)
    assert ahead.compile_stats() == {"decode_signatures": 1,
                                     "prefill_signatures": 1, "retraces": 0}
    if case.startswith("eviction"):
        assert ahead.scheduler.preemptions_total >= 1 <= \
            first.scheduler.preemptions_total, "no eviction was forced"
        assert len(evicted) == ahead.scheduler.preemptions_total \
            + first.scheduler.preemptions_total
        assert _count(ahead, "serving_steps_landed_first_total",
                      reason="preemption") >= 1
    else:
        assert _count(ahead, "serving_steps_landed_first_total",
                      reason="preemption") == 0


@pytest.mark.parametrize("decode_steps", [1, 4])
def test_an_eos_mid_stream_drops_the_rows_dispatched_past_it(engine,
                                                             decode_steps):
    """The token that ends a request lands a step after its dispatch, and
    by then the request has been given one more dispatch: those rows are
    dropped and counted, and the tokens are the landing-first order's."""
    prompts = _prompts(17, (6, 13, 9))
    plain = _server(engine, decode_steps=decode_steps)
    for p in prompts:
        plain.submit(p, max_new_tokens=12)
    greedy = {o.req_id: o.tokens for o in plain.serve_forever()}
    # each request's EOS is its own fourth greedy token (or where that
    # token first shows): mid-stream, with tokens still to come
    eos = {rid: toks[3] for rid, toks in greedy.items()}
    ahead = _server(engine, decode_steps=decode_steps)
    first = _server(engine, decode_steps=decode_steps)
    for srv in (ahead, first):
        for rid, p in enumerate(prompts):
            srv.submit(p, max_new_tokens=12, eos_token_id=eos[rid])
    got = {o.req_id: o for o in ahead.serve_forever()}
    want = {o.req_id: o for o in _serve_landing_first(first)}
    for rid, toks in greedy.items():
        cut = toks[:toks.index(eos[rid]) + 1]
        assert got[rid].tokens == want[rid].tokens == cut
        assert got[rid].finish_reason == want[rid].finish_reason == "eos"
        assert len(cut) <= 4
    rows = _count(ahead, "serving_decode_overrun_tokens_total")
    # landing first a dispatch's own rows past the EOS are all there is;
    # run ahead, every request was dispatched once more besides
    inside = _count(first, "serving_decode_overrun_tokens_total")
    assert (inside == 0) == (decode_steps == 1)
    assert rows >= inside + len(prompts)
    for srv in (ahead, first):
        srv.cache.allocator.check_consistency()
        assert srv.cache.allocator.num_allocated == 0


def test_the_prefix_index_holds_the_same_blocks_in_either_order(engine):
    """Blocks are registered at landing, by their tokens: generated tokens
    reach the index a step later and the index ends up the same."""
    rng = np.random.default_rng(23)
    shared = rng.integers(0, VOCAB, (16,)).astype(np.int32)
    prompts = [np.concatenate([shared, rng.integers(0, VOCAB, (n,))
                               .astype(np.int32)]) for n in (3, 9, 1, 14, 6)]
    config = {"max_batch": 2, "prefix_cache": {"enabled": True}}
    ahead, first = _server(engine, **config), _server(engine, **config)
    for srv in (ahead, first):
        for p in prompts:
            srv.submit(p, max_new_tokens=10)
    got = {o.req_id: o.tokens for o in ahead.serve_forever()}
    want = {o.req_id: o.tokens for o in _serve_landing_first(first)}
    assert got == want
    a, f = ahead.cache.prefix_cache, first.cache.prefix_cache
    assert set(a._index) == set(f._index) and len(a._index) > 2
    assert a.hits > 0 and f.hits > 0
    assert a.insertions == f.insertions
    for srv in (ahead, first):
        srv.cache.allocator.check_consistency()


def test_one_decode_program_whichever_array_is_the_step_before(engine):
    """The first dispatch reads zeros in place of a dispatch before, every
    later one the tokens that the dispatch before left on the device: one
    signature and one backend compile of the decode program, as many as
    the landing-first order makes, which only ever passes the zeros."""
    from deepspeed_tpu.telemetry import compile_watch
    prompts = _prompts(3, LENGTHS)

    def backend_compiles(serve):
        srv = _server(engine)
        for p, kw in zip(prompts, GREEDY):
            srv.submit(p, **kw)
        compile_watch.install_global_listener(srv.registry)
        try:
            assert len(serve(srv)) == len(prompts)
        finally:
            compile_watch.uninstall_global_listener()
        assert srv.compile_stats() == {"decode_signatures": 1,
                                       "prefill_signatures": 1,
                                       "retraces": 0}
        return sum(m.value for ms in srv.registry.collect().values()
                   for m in ms if m.name == "xla_backend_compiles_total")

    assert backend_compiles(ServingEngine.serve_forever) \
        == backend_compiles(_serve_landing_first)


# ------------------------------------------------------ no device call
@pytest.mark.parametrize("seed", [0, 1, 2 ** 31 - 1, 2 ** 31, 2 ** 32 - 1,
                                  2 ** 40 + 5, -1])
def test_the_host_made_rng_lane_is_the_device_made_key(seed):
    assert jax.config.jax_default_prng_impl == "threefry2x32"
    assert not jax.config.jax_enable_x64
    lane = make_rng_lane(seed)
    assert lane.dtype == np.uint32 and lane.shape == (2,)
    np.testing.assert_array_equal(lane,
                                  np.asarray(jax.random.PRNGKey(seed)))


def test_another_key_implementation_keeps_the_device_form():
    with jax.default_prng_impl("rbg"):
        assert jax.config.jax_default_prng_impl == "rbg"
        want = np.asarray(jax.device_get(jax.random.PRNGKey(5)), np.uint32)
        np.testing.assert_array_equal(make_rng_lane(5), want)
        assert want.shape != (2,)


def test_submit_makes_no_device_call(engine):
    """The guard fires on the CPU backend (the device-made key failed it
    with "Disallowed host-to-device transfer")."""
    srv = _server(engine, observability={"enabled": True})
    with jax.transfer_guard("disallow"):
        for n, seed in ((5, 0), (19, 2 ** 40 + 5), (30, -1)):
            srv.submit(np.arange(1, n + 1, dtype=np.int32),
                       max_new_tokens=6, temperature=0.9, seed=seed)
    assert len(srv.serve_forever()) == 3
    srv.close()


class _Watched:
    """A decode dispatch's tokens, counting what reads them back."""

    def __init__(self, tokens, reads):
        self.tokens, self.reads = tokens, reads

    def copy_to_host_async(self):
        self.tokens.copy_to_host_async()

    def __array__(self, dtype=None, copy=None):
        self.reads.append(self)
        return np.asarray(self.tokens)


def test_a_steady_step_reads_back_the_step_before_and_nothing_else(engine):
    """Observability, the ledger and the gauges on: between two decode
    dispatches the host converts exactly one of the program's outputs, the
    tokens of the dispatch before the one it has just made."""
    srv = _server(engine, observability={"enabled": True})
    for p in _prompts(5, (9, 17, 4)):
        srv.submit(p, max_new_tokens=12)
    reads, made = [], []
    decode = srv._decode_fn

    def watched(*args):
        pools, tokens = decode(*(a.tokens if isinstance(a, _Watched) else a
                                 for a in args))
        made.append(_Watched(tokens, reads))
        return pools, made[-1]

    srv._decode_fn = watched
    def every_slot_in_flight():
        return all(r is not None and r.in_flight
                   for r in srv.scheduler.slots)

    while not every_slot_in_flight():
        srv.step()
    steady = 0
    while every_slot_in_flight():
        n_reads, n_made = len(reads), len(made)
        srv.step()
        if len(made) == n_made:
            break                       # a last token in flight: no dispatch
        steady += 1
        assert len(reads) == n_reads + 1
        assert reads[-1] is made[-2], "the landing read this step's own"
        assert srv._in_flight.toks is made[-1]
    assert steady >= 5
    assert len(srv.serve_forever()) == 3
    assert len(reads) == len(made) and not srv._in_flight
