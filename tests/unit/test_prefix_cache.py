"""Shared-prefix KV reuse tests — refcounted allocator, prefix index,
copy-on-write forks, and the SLO-aware router.

Host-side invariants run with no device programs (the allocator, prefix
index and scheduler admission walk are pure bookkeeping): refcount
share/release churn never leaks, the null block is never refcounted, the
double-free guard names the owning request and refcount, all-or-nothing
admission rolls shared references back, cold cached blocks are reclaimed
BEFORE any preemption fires, and preempting one sharer leaves the other
sharers' tables intact. The end-to-end tests drive a real ServingEngine
and pin the acceptance behaviours: greedy outputs bit-exact cache-on vs
cache-off (including across COW forks and preemption/resume) with
exactly one compiled decode program and zero retraces, int8-KV shared
blocks byte-identical to a fresh rewrite of the same prefix, the
``cached_prefill`` ledger category with sums still exact, and router
placement following prefix affinity until a replica reports
``ttft_slo_breach``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                          DeepSpeedServingConfig)
from deepspeed_tpu.serving.kv_cache import (BlockAllocator,
                                            BlockAllocatorError,
                                            PagedKVCache, PrefixCache)
from deepspeed_tpu.serving.router import ServingRouter
from deepspeed_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                             Request, RequestState)
from deepspeed_tpu.serving.server import ServingEngine
from deepspeed_tpu.telemetry.metrics import MetricsRegistry
from deepspeed_tpu.utils import groups


# -------------------------------------------------- refcounted allocator
def test_share_and_release_refcounts():
    a = BlockAllocator(8)
    blocks = a.allocate(2, owner="r1")
    a.share(blocks, owner="r2")
    a.share(blocks, owner="r3")
    assert a.refcount(blocks[0]) == 3
    assert a.num_allocated == 2, "refcounts don't inflate the block count"
    a.free(blocks, owner="r2")
    assert a.refcount(blocks[0]) == 2
    a.free(blocks, owner="r1")
    a.free(blocks, owner="r3")
    assert a.num_allocated == 0 and a.num_free == a.num_usable
    a.check_consistency()


def test_null_block_never_refcounted():
    a = BlockAllocator(4)
    assert 0 not in a.allocate(3)
    with pytest.raises(BlockAllocatorError):
        a.share([0])
    with pytest.raises(BlockAllocatorError):
        a.free([0])
    a.check_consistency()


def test_double_free_names_owner_and_refcount():
    a = BlockAllocator(6)
    blocks = a.allocate(1, owner=7)
    a.free(blocks, owner=7)
    with pytest.raises(BlockAllocatorError) as ei:
        a.free(blocks, owner=7)
    msg = str(ei.value)
    assert "refcount 0" in msg and "request 7" in msg, msg


def test_foreign_free_names_holders():
    a = BlockAllocator(6)
    blocks = a.allocate(1, owner="mine")
    with pytest.raises(BlockAllocatorError) as ei:
        a.free(blocks, owner="thief")
    msg = str(ei.value)
    assert "thief" in msg and "mine" in msg and "refcount 1" in msg, msg
    a.free(blocks, owner="mine")
    a.check_consistency()


def test_share_free_churn_never_leaks():
    rng = np.random.default_rng(2)
    a = BlockAllocator(17)
    live = []                           # (blocks, owner)
    next_owner = 0
    for _ in range(600):
        roll = rng.random()
        if live and roll < 0.35:
            a.free(*live.pop(int(rng.integers(len(live)))))
        elif live and roll < 0.55:
            blocks, _ = live[int(rng.integers(len(live)))]
            owner = f"s{next_owner}"
            next_owner += 1
            a.share(blocks, owner=owner)
            live.append((blocks, owner))
        else:
            owner = f"o{next_owner}"
            next_owner += 1
            got = a.allocate(int(rng.integers(1, 4)), owner=owner)
            if got is not None:
                live.append((got, owner))
        a.check_consistency()
    for blocks, owner in live:
        a.free(blocks, owner=owner)
    a.check_consistency()
    assert a.num_allocated == 0 and a.num_free == a.num_usable


# ----------------------------------------------------------- prefix index
def _pc(num_blocks=32, block_size=4, capacity=0, salt="t"):
    alloc = BlockAllocator(num_blocks)
    return alloc, PrefixCache(alloc, block_size=block_size,
                              capacity_blocks=capacity, salt=salt)


def test_chain_digest_is_position_and_salt_aware():
    _, pc = _pc(salt="a")
    _, pc2 = _pc(salt="b")
    d = pc.chain_digest(None, [1, 2, 3, 4], 0)
    assert pc.chain_digest(None, [1, 2, 3, 4], 4) != d, \
        "same tokens at a different position must not collide"
    assert pc2.chain_digest(None, [1, 2, 3, 4], 0) != d, \
        "different attention/dtype salt must not collide"
    parent = pc.chain_digest(None, [9, 9, 9, 9], 0)
    assert pc.chain_digest(parent, [1, 2, 3, 4], 4) != \
        pc.chain_digest(None, [1, 2, 3, 4], 4), \
        "a block's digest must certify its whole prefix chain"


def test_lookup_walks_longest_chain_and_insert_dedups():
    alloc, pc = _pc()
    blocks = alloc.allocate(3, owner="w")
    tokens = list(range(12))
    d = None
    for j, b in enumerate(blocks):
        d = pc.insert(d, tokens[j * 4:(j + 1) * 4], j * 4, b)
    hit, digests = pc.lookup(tokens + [99, 98])
    assert hit == blocks and len(digests) == 3
    # divergent third block: only the two-block chain matches
    hit2, _ = pc.lookup(tokens[:8] + [77, 77, 77, 77])
    assert hit2 == blocks[:2]
    # identical re-insert keeps the FIRST writer's block (live sharers
    # must never see a remap)
    assert pc.insert(digests[1], tokens[8:12], 8, 31) == digests[2]
    assert pc.lookup(tokens)[0] == blocks
    assert alloc.refcount(blocks[2]) == 2, "dedup must not double-share"


def test_reclaim_lru_first_and_skips_live_sharers():
    alloc, pc = _pc()
    blocks = alloc.allocate(3, owner="w")
    d0 = pc.insert(None, [1, 2, 3, 4], 0, blocks[0])
    pc.insert(None, [5, 6, 7, 8], 0, blocks[1])
    pc.insert(None, [9, 9, 9, 9], 0, blocks[2])
    alloc.free([blocks[0], blocks[2]], owner="w")   # b1 still held by "w"
    pc.lookup([1, 2, 3, 4])                          # touch: b0 now MRU
    assert pc.reclaim(1) == 1
    assert pc.stats()["evictions"] == 1
    # b2 (cold) went first; b0 (touched) survived; b1 (shared) untouched
    assert pc.lookup([1, 2, 3, 4])[0] == [blocks[0]]
    assert pc.lookup([9, 9, 9, 9])[0] == []
    assert alloc.refcount(blocks[1]) == 2
    assert pc.reclaim(5) == 1, "only b0 is reclaimable; b1 is live"
    alloc.free([blocks[1]], owner="w")
    assert pc.drop_all() == 1
    alloc.check_consistency()
    assert alloc.num_allocated == 0


def test_capacity_bound_evicts_cold_never_live():
    alloc, pc = _pc(capacity=2)
    blocks = alloc.allocate(3, owner="w")
    pc.insert(None, [1, 1, 1, 1], 0, blocks[0])
    pc.insert(None, [2, 2, 2, 2], 0, blocks[1])
    alloc.free([blocks[0]], owner="w")       # only b0 is cold
    pc.insert(None, [3, 3, 3, 3], 0, blocks[2])
    assert pc.resident_blocks() == 2 and pc.stats()["evictions"] == 1
    assert pc.lookup([1, 1, 1, 1])[0] == []
    # every entry live: a further insert is SKIPPED, never steals
    blocks2 = alloc.allocate(1, owner="w")
    pc.insert(None, [4, 4, 4, 4], 0, blocks2[0])
    assert pc.resident_blocks() == 2
    assert pc.lookup([4, 4, 4, 4])[0] == []


# ------------------------------------------------- scheduler admission
def _host_cache(num_blocks=17, block_size=4, prefix=True):
    cache = PagedKVCache(n_layer=1, n_head=1, head_dim=4,
                         block_size=block_size, num_blocks=num_blocks)
    if prefix:
        cache.attach_prefix_cache()
    return cache


def _req(i, prompt, max_new=4):
    return Request(req_id=i, prompt=list(prompt), max_new_tokens=max_new)


def _index_prompt(cache, req):
    """Register a slotted request's FULL prompt blocks (what the server
    does as prefill chunks complete)."""
    pc, bs = cache.prefix_cache, cache.block_size
    d = None
    full = req.full_prompt
    for j in range(len(full) // bs):
        d = pc.insert(d, full[j * bs:(j + 1) * bs], j * bs,
                      req.block_table[j])
    return d


def test_admission_maps_shared_prefix_read_only():
    cache = _host_cache()
    sched = ContinuousBatchingScheduler(cache, max_batch=2,
                                        max_model_len=64)
    prefix = list(range(1, 9))                       # 2 full blocks
    sched.submit(_req(0, prefix + [20, 21]))
    sched.schedule()
    r0 = sched.slots[0]
    _index_prompt(cache, r0)
    sched.submit(_req(1, prefix + [30, 31, 32]))
    sched.schedule()
    r1 = sched.slots[1]
    assert r1.prefix_hit_blocks == 2
    assert r1.block_table[:2] == r0.block_table[:2], \
        "hit blocks map into the sharer's table"
    assert r1.cached_len == 8, "prefill starts at the first uncached token"
    assert r1.cow_fork is None
    assert cache.allocator.refcount(r0.block_table[0]) == 3  # r0+r1+index
    # preempting the SHARER leaves the owner's table intact
    shared_ids = list(r0.block_table[:2])
    state_before = r0.state
    sched._preempt(r1, "test")
    assert r0.block_table[:2] == shared_ids and \
        r0.state is state_before, \
        "preempting a sharer must not disturb the block owner"
    assert cache.allocator.refcount(r0.block_table[0]) == 2
    sched.finish(r0, "max_tokens")
    cache.prefix_cache.drop_all()
    cache.allocator.check_consistency()
    assert cache.allocator.num_allocated == 0


def test_fully_cached_prompt_plans_exactly_one_cow_fork():
    cache = _host_cache()
    sched = ContinuousBatchingScheduler(cache, max_batch=2,
                                        max_model_len=64)
    prompt = list(range(1, 9))                       # exactly 2 blocks
    sched.submit(_req(0, prompt))
    sched.schedule()
    r0 = sched.slots[0]
    _index_prompt(cache, r0)
    sched.submit(_req(1, list(prompt)))
    plan = sched.schedule()
    r1 = sched.slots[1]
    # the last position must be rewritten (it produces the first logits):
    # table = shared chain with its tail swapped for a fresh fork target
    assert plan.cow_forks == [r1]
    src, idx = r1.cow_fork
    assert src == r0.block_table[1] and idx == 1
    assert r1.block_table[0] == r0.block_table[0]
    assert r1.block_table[1] != r0.block_table[1]
    assert r1.cached_len == len(prompt) - 1
    assert r1.shared_blocks == 1
    assert r1.state is RequestState.RUNNING, \
        "one-position rewrite rides the decode step, not a prefill chunk"
    # the fork source carries r1's pinning reference until the copy lands
    assert cache.allocator.refcount(src) == 3
    # preempt r1 BEFORE the copy lands: the pending fork reference and
    # the fresh target must both release (server never ran)
    sched._preempt(r1, "test")
    assert cache.allocator.refcount(src) == 2
    sched.finish(r0, "max_tokens")
    cache.prefix_cache.drop_all()
    cache.allocator.check_consistency()
    assert cache.allocator.num_allocated == 0


def test_admission_rollback_is_all_or_nothing_under_sharing():
    # pool sized so the sharer's MATCH fits but its fresh tail does not
    cache = _host_cache(num_blocks=6)                # 5 usable
    sched = ContinuousBatchingScheduler(cache, max_batch=2,
                                        max_model_len=64)
    prefix = list(range(1, 9))                       # 2 blocks
    sched.submit(_req(0, prefix + [20, 21], max_new=2))   # 3 blocks
    sched.schedule()
    r0 = sched.slots[0]
    _index_prompt(cache, r0)
    base_rc = cache.allocator.refcount(r0.block_table[0])
    # needs 2 shared + 3 fresh with only 2 free -> must roll back fully
    # (the index's own references keep every block rc>=2: nothing is
    # reclaimable, so the grant genuinely cannot be met)
    sched.submit(_req(1, prefix + list(range(30, 41)), max_new=2))
    sched.schedule()
    assert sched.slots[1] is None and len(sched.waiting) == 1
    assert cache.allocator.refcount(r0.block_table[0]) == base_rc, \
        "failed admission must release the shared references it took"
    assert sched.preemptions_total == 0
    cache.allocator.check_consistency()


def test_cold_cached_blocks_reclaimed_before_preemption():
    cache = _host_cache(num_blocks=7)                # 6 usable
    sched = ContinuousBatchingScheduler(cache, max_batch=2,
                                        max_model_len=64)
    pc = cache.prefix_cache
    # a finished request's prefix stays warm: 4 cache-only blocks
    sched.submit(_req(0, list(range(1, 17)), max_new=1))
    sched.schedule()
    r0 = sched.slots[0]
    _index_prompt(cache, r0)
    sched.finish(r0, "max_tokens")
    assert pc.reclaimable_blocks() == 4
    assert cache.allocator.num_free == 2
    # a DIFFERENT 3-block prompt: admission must reclaim cold cache
    # blocks instead of failing or preempting
    sched.submit(_req(1, list(range(50, 61)), max_new=2))
    sched.schedule()
    assert sched.slots[0] is not None or sched.slots[1] is not None
    assert sched.preemptions_total == 0, \
        "a cold cached block is free capacity, not a preemption reason"
    assert pc.stats()["evictions"] >= 1


# ------------------------------------------------------------ end-to-end
@pytest.fixture(scope="module")
def tiny_engine():
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    return cfg, eng


def _baseline(eng, prompt, n_new):
    out = eng.generate(jnp.asarray(prompt, jnp.int32)[None],
                       max_new_tokens=n_new)
    return np.asarray(out)[0, len(prompt):].tolist()


def _cache_on(eng, **over):
    cfg = {"max_batch": 2, "block_size": 8, "prefill_chunk": 6,
           "prefix_cache": {"enabled": True}, **over}
    return ServingEngine(eng, config=cfg, registry=MetricsRegistry())


@pytest.mark.parametrize("int8_kv", [False, True],
                         ids=["kv-float", "kv-int8"])
def test_cow_fork_copies_every_layers_rows_and_nothing_else(int8_kv):
    """The fork's device copy on pools whose rows are padded (5 heads of
    64 = 2.5 lanes): block ``src``'s rows of EVERY layer land in block
    ``dst`` of the same layer, in every leaf (K, V and the int8 scales),
    and no other row of any pool changes."""
    from deepspeed_tpu.serving.runner import PagedGPT2Runner
    L, N, BS, src, dst = 3, 7, 4, 2, 5
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=320,
                     n_layer=L, n_head=5)
    cache = PagedKVCache(L, 5, 64, BS, N, dtype=jnp.float32,
                         int8_kv=int8_kv)
    runner = PagedGPT2Runner(GPT2LMHeadModel(cfg), cache)
    rng = np.random.default_rng(17)
    before = {name: rng.integers(-100, 100, p.shape).astype(p.dtype)
              for name, p in cache.init_pools().items()}
    assert before["k"].shape == (L * N, BS, 384)
    after = runner.copy_block(
        {name: jnp.asarray(p) for name, p in before.items()}, src, dst)
    dst_rows = np.arange(L) * N + dst
    for name, old in before.items():
        new = np.asarray(after[name])
        assert np.array_equal(new[dst_rows], old[np.arange(L) * N + src])
        untouched = np.setdiff1d(np.arange(L * N), dst_rows)
        assert np.array_equal(new[untouched], old[untouched]), name


def test_e2e_cow_parity_one_program_and_counters(tiny_engine):
    """The acceptance guard: shared-prefix traffic (including a
    fully-cached prompt, the COW-fork path) stays greedy-bit-exact vs
    cache-off, with exactly one compiled decode program and zero
    retraces — and the hit/miss/shared gauges flow through the
    registry."""
    cfg, eng = tiny_engine
    rng = np.random.default_rng(3)
    prefix = rng.integers(0, 256, (24,)).astype(np.int32)   # 3 blocks
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 256, (t,)).astype(np.int32)])
               for t in (5, 3, 7)]
    prompts.append(prefix.copy())            # fully cached -> COW fork
    srv = _cache_on(eng)
    rids = [srv.submit(p, max_new_tokens=4) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 4), rid
    pc = srv.cache.prefix_cache
    assert pc.hits > 0 and pc.cow_forks >= 1
    assert srv.compile_stats() == {"decode_signatures": 1,
                                   "prefill_signatures": 1, "retraces": 0}
    snap = srv.registry.snapshot()
    assert snap["serving_prefix_cache_hits_total"][0]["value"] == pc.hits
    assert snap["serving_prefix_cache_misses_total"][0]["value"] == \
        pc.misses
    assert "serving_prefix_blocks_shared" in snap
    assert srv._engine_state()["prefix_cache"]["hit_rate"] == \
        pc.stats()["hit_rate"]
    # drained: every resident entry is cache-only; teardown leaks nothing
    assert pc.shared_blocks() == 0
    pc.drop_all()
    srv.cache.allocator.check_consistency()
    assert srv.cache.allocator.num_allocated == 0


def test_e2e_preemption_with_sharing_stays_exact(tiny_engine):
    """Tiny pool + shared prefixes: preemption of sharing requests (and
    resume onto re-matched cached blocks) must keep greedy parity, and
    the refcounted teardown must drain completely."""
    cfg, eng = tiny_engine
    srv = _cache_on(eng, num_blocks=7)       # 6 usable x 8 = 48 positions
    rng = np.random.default_rng(5)
    prefix = rng.integers(0, 256, (16,)).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(0, 256, (3,)).astype(np.int32)])
               for _ in range(2)]
    rids = [srv.submit(p, max_new_tokens=18) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    assert srv.scheduler.preemptions_total >= 1, \
        "scenario must actually exercise preemption under sharing"
    for rid, p in zip(rids, prompts):
        assert outs[rid].tokens == _baseline(eng, p, 18), rid
    assert srv.compile_stats()["retraces"] == 0
    srv.cache.prefix_cache.drop_all()
    srv.cache.allocator.check_consistency()
    assert srv.cache.allocator.num_allocated == 0


def test_e2e_cached_prefill_ledger_category_sums_exact(tiny_engine):
    """The PR-9 satellite: cache-hit requests book their remaining
    prefill as ``cached_prefill`` and the slot-step ledger's
    by-construction sum survives the new category."""
    cfg, eng = tiny_engine
    srv = _cache_on(eng, observability={
        "enabled": True, "window": 8, "ttft_slo_ms": 1e12,
        "trace_lanes": False, "snapshot_file": "/tmp/_pfx_health.json"})
    rng = np.random.default_rng(7)
    prefix = rng.integers(0, 256, (16,)).astype(np.int32)
    # drain the cold request FIRST so the second one actually hits
    # (concurrent admissions of the same prefix all miss by design)
    for t in (6, 9):
        srv.submit(np.concatenate(
            [prefix, rng.integers(0, 256, (t,)).astype(np.int32)]),
            max_new_tokens=3)
        srv.serve_forever()
    assert srv.cache.prefix_cache.hits > 0
    units, steps = srv.observatory.ledger.totals()
    assert units["cached_prefill"] > 0, \
        "hit requests must book cached_prefill, not plain prefill"
    assert units["prefill"] > 0, "the cold first request stays prefill"
    assert sum(units.values()) == steps * srv.max_batch * 1
    srv.close()


def test_e2e_int8_shared_blocks_bit_exact():
    """Quantize-on-write determinism: the int8 bytes (and fp32 scales) a
    SHARED prefix block carries must equal what a fresh engine writes
    for the same prompt — a reader cannot tell a shared block from one
    it wrote itself."""
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2, kv_cache_dtype="int8")
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(2),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.int8)
    prompt = np.asarray(
        np.random.default_rng(11).integers(0, 256, (16,)), np.int32)

    def prefix_pool_bytes(srv, blocks):
        rows = np.asarray(srv.cache.layer_rows(
            np.asarray(blocks), n_layers=cfg.n_layer))
        return {name: np.asarray(p)[rows]
                for name, p in srv.pools.items()}

    srv_a = _cache_on(eng)
    assert srv_a.cache.int8_kv
    rid = srv_a.submit(prompt, max_new_tokens=4)
    outs_a = {o.req_id: o for o in srv_a.serve_forever()}
    pc = srv_a.cache.prefix_cache
    shared_blocks, _ = pc.lookup(list(prompt))
    assert len(shared_blocks) == 2, "both full prompt blocks must index"
    a_bytes = prefix_pool_bytes(srv_a, shared_blocks)

    # a fresh cache-OFF engine writes the same prompt from scratch
    srv_b = ServingEngine(eng, config={"max_batch": 2, "block_size": 8,
                                       "prefill_chunk": 6},
                          registry=MetricsRegistry())
    srv_b.submit(prompt, max_new_tokens=4)     # stays live past prefill
    while srv_b.scheduler.num_active == 0:
        srv_b.step()
    r = next(r for r in srv_b.scheduler.slots if r is not None)
    while r.cached_len < 16:
        srv_b.step()
    b_bytes = prefix_pool_bytes(srv_b, r.block_table[:2])
    for name in a_bytes:
        assert np.array_equal(a_bytes[name], b_bytes[name]), \
            f"pool {name!r} diverged — int8 blocks must share bit-exactly"
    # and the sharing path itself stays token-exact
    rid2 = srv_a.submit(prompt, max_new_tokens=4)
    outs2 = {o.req_id: o for o in srv_a.serve_forever()}
    assert outs2[rid2].tokens == outs_a[rid].tokens


# ---------------------------------------------------------------- router
def test_router_prefers_prefix_affinity(tiny_engine):
    cfg, eng = tiny_engine
    replicas = [_cache_on(eng), _cache_on(eng)]
    router = ServingRouter(replicas)
    rng = np.random.default_rng(13)
    prefix = rng.integers(0, 256, (16,)).astype(np.int32)
    # warm ONLY replica 1's cache through the router's own placement
    replicas[1].submit(np.concatenate(
        [prefix, rng.integers(0, 256, (4,)).astype(np.int32)]),
        max_new_tokens=2)
    while replicas[1].scheduler.has_work():
        replicas[1].step()
    replicas[1].collect()
    d = router.explain(list(np.concatenate([prefix, [1, 2, 3]])))
    assert d.replica == 1 and d.affinity_blocks == 2
    rid = router.submit(np.concatenate(
        [prefix, rng.integers(0, 256, (5,)).astype(np.int32)]),
        max_new_tokens=3)
    outs = {o.req_id: o for o in router.serve_forever()}
    assert rid in outs
    assert router.routed_by_replica == [0, 1]


def test_router_fails_over_on_ttft_slo_breach(tiny_engine):
    """A replica whose observatory fired ttft_slo_breach recently loses
    routing even when it holds the longest prefix — unless every replica
    is breaching (failover, not blacklist)."""
    cfg, eng = tiny_engine
    breaching = _cache_on(eng, observability={
        "enabled": True, "window": 2, "warmup_windows": 0,
        "ttft_slo_ms": 1e-6, "ttft_breach_frac": 0.5,
        "trace_lanes": False, "snapshot_file": "/tmp/_pfx_breach.json"})
    healthy = _cache_on(eng)
    router = ServingRouter([breaching, healthy])
    rng = np.random.default_rng(17)
    prefix = rng.integers(0, 256, (16,)).astype(np.int32)
    # drive the breaching replica directly: every TTFT breaches 1e-6 ms
    breaching.submit(np.concatenate(
        [prefix, rng.integers(0, 256, (4,)).astype(np.int32)]),
        max_new_tokens=4)
    while breaching.scheduler.has_work():
        breaching.step()
    breaching.collect()
    assert breaching.router_signals()["ttft_slo_breach"] is True
    assert healthy.router_signals()["ttft_slo_breach"] is False
    # despite full prefix affinity on the breaching replica, placement
    # fails over to the healthy one
    d = router.explain(list(np.concatenate([prefix, [1, 2]])))
    assert d.replica == 1
    # ... but when EVERY replica breaches, the least-bad one still serves
    assert router.explain(list(prefix)).scores[0] < 0
    breaching.close()


def test_tune_serving_scores_tok_s_under_ttft_constraint(tiny_engine):
    from deepspeed_tpu.autotuning.tune import (SERVING_TUNE_SCHEMA,
                                               tune_serving)
    cfg, eng = tiny_engine
    rng = np.random.default_rng(19)
    reqs = [{"prompt": rng.integers(0, 256, (6,)).tolist(),
             "max_new_tokens": 3} for _ in range(3)]
    best, report = tune_serving(
        eng, reqs, space={"max_batch": [2], "decode_steps": [1, 2]},
        ttft_slo_ms=1e9,
        base_config={"block_size": 8, "prefill_chunk": 6})
    assert report["schema"] == SERVING_TUNE_SCHEMA
    assert len(report["candidates"]) == 2
    assert report["winner"]["feasible"] is True
    assert best["max_batch"] == 2
    # an unmeetable constraint rejects everyone but still names a winner
    _, strict = tune_serving(
        eng, reqs, space={"max_batch": [2], "decode_steps": [1]},
        ttft_slo_ms=1e-6,
        base_config={"block_size": 8, "prefill_chunk": 6})
    assert strict["winner"]["feasible"] is False
    assert all(c["reject_reason"] == "ttft"
               for c in strict["candidates"])


# ---------------------------------------------------------------- config
def test_prefix_cache_and_router_config_blocks(monkeypatch):
    c = DeepSpeedServingConfig({"serving": {
        "prefix_cache": {"enabled": True, "capacity_blocks": 64},
        "router": {"replicas": 3, "affinity_weight": 1.5}}})
    assert c.prefix_cache.enabled and c.prefix_cache.capacity_blocks == 64
    assert c.router.replicas == 3 and c.router.affinity_weight == 1.5
    assert c.router.breach_penalty == 100.0
    monkeypatch.setenv("DS_SERVING_PREFIX_CACHE", "0")
    assert not DeepSpeedServingConfig(
        {"serving": {"prefix_cache": {"enabled": True}}}).prefix_cache.enabled
    monkeypatch.setenv("DS_SERVING_PREFIX_CACHE", "1")
    assert DeepSpeedServingConfig({}).prefix_cache.enabled
    monkeypatch.delenv("DS_SERVING_PREFIX_CACHE")
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig(
            {"serving": {"prefix_cache": {"capacity_blocks": -1}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig({"serving": {"router": {"replicas": 0}}})
    with pytest.raises(DeepSpeedConfigError):
        DeepSpeedServingConfig(
            {"serving": {"router": {"queue_weight": -2.0}}})
