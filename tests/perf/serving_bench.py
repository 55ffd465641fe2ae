"""Serving acceptance benchmark — continuous batching vs batch-synchronous.

Replays ONE mixed-length request trace (heterogeneous prompt and
generation lengths, all submitted at t=0) through both inference paths at
equal max batch:

* **baseline**: the batch-synchronous ``InferenceEngine.generate()`` —
  requests grouped FCFS into fixed batches, prompts padded to a 32-token
  bucket, every batch decoded to its LONGEST member's generation length
  (head-of-line blocking is the cost being measured, so the padded/wasted
  steps are the point, not an artifact). The API delivers all tokens at
  ``generate()`` return, so a request's TTFT is its batch's completion
  time — that is really when the first token becomes visible.
* **serving**: the continuous-batching ServingEngine over the paged KV
  cache — slots refill the moment a request finishes, prefill is chunked,
  and TTFT/inter-token latency are measured per request.

Both sides are warmed first (XLA compile excluded from the timed run) and
both count only USEFUL tokens (each request's own generation length).

The serving side runs with the serving observatory's slot-step ledger
armed, and the artifact carries the timed trace's slot-step attribution
(decode_useful / prefill / recompute / frozen / idle in integer
micro-units) — the instrument that would catch a regression back toward
the static baseline's measured ~76% wasted slot-steps.

Writes the committed SERVING_BENCH.json (schema-pinned in
tests/unit/test_artifacts.py with floors that encode the acceptance
criteria: strictly higher aggregate tok/s, exactly one compiled decode
program, zero retraces, slot-step categories summing EXACTLY to
steps x max_batch x decode_steps, serving's wasted fraction below the
baseline's) and REFUSES to write a regen where continuous batching does
not win, the categories don't sum, or serving wastes as much as the
static baseline.

A second, shared-prefix trace (a pool of long common prefixes + short
unique tails — the system-prompt/few-shot production shape) replays
twice at EQUAL config, prefix cache off then on, and the artifact's
``prefix_cache`` section carries the A/B: hit rate, COW forks, peak
shared blocks, and TTFT p50 both ways. The regen refuses an artifact
where the cached run's TTFT p50 is not strictly better or either run's
slot-step categories stop summing exactly. A router section reports
aggregate tok/s for 1 vs 2 cache-armed replicas behind the
prefix-affinity ServingRouter on the same trace shape.

A third, speculative A/B section replays the SAME decode-heavy trace
through a wider model (n_embd 512 — the weight-bandwidth-bound regime
the technique targets) with speculation off then on at max_batch 1 and
4. The spec-off arm decodes ``k+1`` tokens per dispatch (the existing
multi-token scan) so both arms amortise host dispatch over identical
token counts — the measured win is draft-layers-vs-all-layers compute,
not dispatch accounting. The bench model is random-init, so the
truncated-layer self-draft is made representative the honest way: the
attn/mlp output-projection kernels of every layer ABOVE ``draft_layers``
are damped (x0.4), making the draft's layer-prefix dominate the target
logits the same way a well-trained draft tracks its target (~97%
measured acceptance, with real rejections booked). Greedy parity is
asserted token-for-token between the arms, and the regen REFUSES an
artifact where spec-on loses the 1.5x floor at either batch size, the
steady state is not exactly {1 draft, 1 verify} programs / 0 retraces,
either arm's slot-step categories stop summing exactly, no rejections
were booked, or parity breaks.

Run:  JAX_PLATFORMS=cpu python tests/perf/serving_bench.py        # laptop
      python tests/perf/serving_bench.py                          # TPU
Env:  SERVING_BENCH_OUT (default SERVING_BENCH.json at the repo root),
      SERVING_BENCH_MODEL ("bench-small" default; any PRESETS name),
      SERVING_BENCH_N (requests, default 96), SERVING_BENCH_BATCH
      (max batch, default 8), SERVING_BENCH_KV (auto|int8),
      SERVING_BENCH_DECODE_STEPS (tokens per decode dispatch, default 8),
      SERVING_BENCH_PREFIX_N / _PREFIX_POOL / _PREFIX_LEN / _REUSE
      (shared-prefix trace: requests 64, pool 4, prefix length 96,
      reuse ratio 0.9), SERVING_BENCH_ROUTER_N (router trace size, 32),
      SERVING_BENCH_SPEC_K (drafted tokens per dispatch, default 6),
      SERVING_BENCH_SPEC_LAYERS (self-draft depth, default 1),
      SERVING_BENCH_SPEC_DAMP (tail damping factor, default 0.4),
      SERVING_BENCH_SPEC_GEN (tokens per request, default 96),
      SERVING_BENCH_SPEC_REPS (best-of replays per arm, default 3),
      BENCH_OBS_SERVER=1 (opt-in: replay the timed trace once more with
      the live obs endpoint armed and a background scraper polling
      /metrics + /api/report/serving; records the measured tok/s delta
      in an ``obs_server`` artifact section and REFUSES the regen when
      answering scrapes costs more than 2% throughput).
"""

import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

PROMPT_BUCKET = 32         # baseline pads prompts to this multiple
OBS_SCRAPE_INTERVAL_S = 0.5   # obs-server arm: aggressive dashboard rate


def _exact_percentile(values, q):
    return float(np.percentile(np.asarray(values, np.float64), q * 100))


def _r(x, digits=2):
    """round() that passes None through (an empty histogram — e.g. a
    decode_steps large enough that every request finishes in its first
    dispatch — yields no inter-token observations)."""
    return None if x is None else round(x, digits)


@dataclasses.dataclass
class TraceReq:
    prompt: np.ndarray
    gen: int


def build_trace(n, vocab, max_batch, seed=0):
    """Mixed-length trace, the production chat shape scaled to the bench
    model: prompts 8-64, generations BIMODAL — mostly short answers
    (8-24) with a steady third of long ones (128, the 16x spread of the
    reference trace). Long requests are staggered so every FCFS batch
    window contains several (static batches always decode to the long
    length while their short slots sit finished), and there are exactly
    ``max_batch`` of them in total so the continuous batcher can retire
    the shorts early and keep EVERY slot busy on the long tail."""
    rng = np.random.default_rng(seed)
    prompt_lens = rng.integers(8, 65, n)
    gen_lens = rng.integers(8, 25, n)
    # one long generation per FCFS batch window: every static batch pads
    # its 7 short slots to 128 steps, while the continuous batcher holds
    # all the (overlapping) longs concurrently once the shorts retire
    gen_lens[::max_batch] = 128
    return [TraceReq(rng.integers(0, vocab, (int(p),)).astype(np.int32),
                     int(g)) for p, g in zip(prompt_lens, gen_lens)]


def build_prefix_trace(n, vocab, prefix_pool=4, prefix_len=96,
                       reuse_ratio=0.9, seed=1):
    """Shared-prefix trace: a pool of ``prefix_pool`` common prefixes of
    ``prefix_len`` tokens (system prompts / few-shot templates); each
    request draws one + a short unique tail with probability
    ``reuse_ratio``, else a fully unique prompt. Tails stop at 31 tokens
    so with block_size 32 every FULL prompt block belongs to the shared
    prefix — the trace measures prefix reuse, not accidental tail
    collisions. Deterministic in ``seed``."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab, (prefix_len,)).astype(np.int32)
                for _ in range(prefix_pool)]
    out = []
    for _ in range(n):
        if rng.random() < reuse_ratio:
            head = prefixes[int(rng.integers(prefix_pool))]
            tail = rng.integers(
                0, vocab, (int(rng.integers(8, 32)),)).astype(np.int32)
            prompt = np.concatenate([head, tail])
        else:
            prompt = rng.integers(
                0, vocab, (int(rng.integers(16, 129)),)).astype(np.int32)
        out.append(TraceReq(prompt, int(rng.integers(8, 17))))
    return out


def run_baseline(eng, trace, max_batch):
    """Batch-synchronous: FCFS groups of max_batch, padded prompts,
    decode to the batch max gen. Returns (elapsed_s, ttfts_s, waste)."""
    import jax
    import jax.numpy as jnp
    batches = [trace[i:i + max_batch]
               for i in range(0, len(trace), max_batch)]

    def run_batch(batch):
        plen = max(len(r.prompt) for r in batch)
        plen = -(-plen // PROMPT_BUCKET) * PROMPT_BUCKET
        gen = max(r.gen for r in batch)
        ids = np.zeros((len(batch), plen), np.int32)
        for i, r in enumerate(batch):
            ids[i, plen - len(r.prompt):] = r.prompt    # left-pad
        out = eng.generate(jnp.asarray(ids), max_new_tokens=gen)
        jax.device_get(out[0, -1])
        return len(batch) * gen

    for b in batches:                       # warm every program
        run_batch(b)
    t0 = time.perf_counter()
    ttfts, decoded = [], 0
    for b in batches:
        decoded += run_batch(b)
        done = time.perf_counter() - t0
        ttfts.extend([done] * len(b))       # tokens visible at batch end
    elapsed = time.perf_counter() - t0
    useful = sum(r.gen for r in trace)
    return elapsed, ttfts, 1.0 - useful / decoded


def run_serving(make_engine, trace, sample=None):
    """Continuous batching: submit the whole trace at t=0, drive step()
    while sampling KV occupancy (plus an optional per-step ``sample``
    hook — the prefix A/B uses it to catch peak shared blocks, which
    are 0 again once the trace drains)."""
    srv = make_engine()
    # warm both compiled programs outside the timed window
    srv.submit(trace[0].prompt[:9], max_new_tokens=2)
    while srv.scheduler.has_work():
        srv.step()
    srv.collect()
    # counter/ledger baselines: the artifact reports the TIMED trace's
    # work, not the warm-up request's dispatches
    warm = {name: srv.registry.counter(name).value
            for name in ("serving_decode_steps_total",
                         "serving_prefill_chunks_total")}
    warm_units, warm_steps = srv.observatory.ledger.totals()
    warm["slot_units"], warm["slot_steps"] = warm_units, warm_steps
    t0 = time.perf_counter()
    rids = [srv.submit(r.prompt, max_new_tokens=r.gen) for r in trace]
    occ = []
    while srv.scheduler.has_work():
        srv.step()
        occ.append(srv.cache.allocator.occupancy())
        if sample is not None:
            sample(srv)
    elapsed = time.perf_counter() - t0
    outs = {o.req_id: o for o in srv.collect()}
    assert set(rids) == set(outs), "trace must fully drain"
    assert all(len(outs[r].tokens) == t.gen
               for r, t in zip(rids, trace)), "wrong token counts"
    return srv, elapsed, [outs[r].ttft_s for r in rids], occ, warm


def slot_steps_of(srv, warm, max_batch, K):
    """The timed trace's slot-step attribution (warm-up diffed out):
    integer micro-units, so the sums-to-total check is EXACT."""
    units_all, steps_all = srv.observatory.ledger.totals()
    units = {c: units_all[c] - warm["slot_units"][c] for c in units_all}
    sched_steps = steps_all - warm["slot_steps"]
    total_units = sum(units.values())
    wasted_units = (units["idle"] + units["frozen"] + units["recompute"]
                    + units.get("drafted_rejected", 0))
    return {
        "steps": sched_steps,
        "max_batch": max_batch,
        "decode_steps": K,
        "units": units,
        "total_units": total_units,
        "expected_units": sched_steps * max_batch * K,
        "sums_exact": total_units == sched_steps * max_batch * K,
        "wasted_frac": round(wasted_units / max(1, total_units), 4),
    }


def run_obs_scraped(eng, serving_cfg, trace):
    """BENCH_OBS_SERVER=1 arm: interleaved A/B pairs on the same timed
    trace — replays with no server alternating with replays where the
    live observability endpoint is armed on the serving registry and a
    background scraper polls ``/metrics`` + ``/api/report/serving``
    twice a second (an aggressive dashboard cadence; Prometheus default
    is 15 s). Three pairs, best-of per arm: a scheduler hiccup on
    either side can neither fake nor mask a regression on a ~4 s CPU
    replay. Returns (off_elapsed_s, on_elapsed_s, stats)."""
    import http.client
    import threading

    from deepspeed_tpu.serving.server import ServingEngine
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry
    from deepspeed_tpu.telemetry.obs_server import ObsServer

    scrapes = {"n": 0, "errors": 0}

    def run_off():
        _, elapsed, _, _, _ = run_serving(
            lambda: ServingEngine(eng, config=dict(serving_cfg),
                                  registry=MetricsRegistry()), trace)
        return elapsed

    def run_on():
        registry = MetricsRegistry()
        obs = ObsServer(registry=registry)
        stop = threading.Event()

        def scraper():
            # one keep-alive connection for the whole run, exactly like a
            # real Prometheus scraper — a fresh connection per request
            # would bill client-side setup and server thread churn to the
            # scrape cost
            conn = http.client.HTTPConnection(
                obs.url.split("//", 1)[1], timeout=2.0)
            while not stop.is_set():
                for path in ("/metrics", "/api/report/serving"):
                    try:
                        conn.request("GET", path)
                        conn.getresponse().read()
                        # any answered status counts (404 until the
                        # engine registers its provider) — still costed
                        scrapes["n"] += 1
                    except Exception:
                        scrapes["errors"] += 1
                        conn.close()        # reconnect on next request
                stop.wait(OBS_SCRAPE_INTERVAL_S)
            conn.close()

        thread = threading.Thread(target=scraper, daemon=True,
                                  name="bench-obs-scraper")
        thread.start()
        try:
            _, elapsed, _, _, _ = run_serving(
                lambda: ServingEngine(eng, config=dict(serving_cfg),
                                      registry=registry, obs_server=obs),
                trace)
        finally:
            stop.set()
            thread.join(timeout=5.0)
            obs.close()
        return elapsed

    offs, ons = [], []
    for _ in range(3):
        offs.append(run_off())
        ons.append(run_on())
    return min(offs), min(ons), dict(scrapes, pairs=len(offs))


def _anatomy_shares(srv, trace):
    """Per-category device-time shares from a bounded profiler capture
    around live serving steps (``ServingEngine.profile_window``).
    Work is queued first so the annotated steps execute real dispatches;
    tolerates an unavailable profiler (CPU wheels without programmatic
    capture) by reporting ``{"enabled": False}``."""
    for r in trace[:2]:
        srv.submit(r.prompt, max_new_tokens=r.gen)
    rep = srv.profile_window(
        steps=4, write=False,
        out=os.path.join("/tmp", "serving_bench_spec_anatomy",
                         "anatomy.json"))
    while srv.scheduler.has_work():
        srv.step()
    srv.collect()
    if not rep.get("enabled"):
        return {"enabled": False, "reason": rep.get("reason")}
    cats = rep.get("categories_s", {})
    tot = sum(cats.values()) or 1.0
    return {"enabled": True,
            "shares": {c: round(v / tot, 4) for c, v in cats.items()}}


def run_spec_arm(eng, max_batch, trace, k, draft_layers, spec, reps,
                 anatomy=False):
    """One speculative-A/B arm: a warm ServingEngine replayed ``reps``
    times on the same trace (best-of timing — CPU scheduler hiccups
    can neither fake nor mask the win), slot-step ledger read over the
    whole timed window (sums stay exact by construction across reps).
    The spec-off arm runs the plain multi-token scan at
    ``decode_steps=k+1`` so both arms deliver identical tokens per
    dispatch."""
    from deepspeed_tpu.serving.server import ServingEngine
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry

    cfg = {"max_batch": max_batch, "block_size": 32, "prefill_chunk": 64,
           "max_model_len": 256,
           "decode_steps": 1 if spec else k + 1,
           "observability": {
               "enabled": True, "window": 32,
               "ttft_slo_ms": 1e12, "preemption_thrash": 10 ** 9,
               "no_progress_steps": 10 ** 9, "trace_lanes": False,
               "snapshot_file": os.path.join(
                   "/tmp", "serving_bench_spec_health.json")}}
    if spec:
        cfg["speculative"] = {"enabled": True, "k": k,
                              "draft_layers": draft_layers}
    srv = ServingEngine(eng, config=cfg, registry=MetricsRegistry())
    srv.submit(trace[0].prompt[:9], max_new_tokens=2)
    while srv.scheduler.has_work():
        srv.step()
    srv.collect()
    warm_units, warm_steps = srv.observatory.ledger.totals()
    warm = {"slot_units": warm_units, "slot_steps": warm_steps}
    best, toks = None, None
    for _ in range(reps):
        t0 = time.perf_counter()
        rids = [srv.submit(r.prompt, max_new_tokens=r.gen)
                for r in trace]
        while srv.scheduler.has_work():
            srv.step()
        elapsed = time.perf_counter() - t0
        outs = {o.req_id: o for o in srv.collect()}
        assert set(rids) == set(outs), "spec trace must fully drain"
        toks = [outs[r].tokens for r in rids]
        best = elapsed if best is None else min(best, elapsed)
    useful = sum(r.gen for r in trace)
    # ledger/stats read BEFORE the anatomy window so profiling steps
    # don't leak into the timed attribution
    slots = slot_steps_of(srv, warm, max_batch, k + 1)
    arm = {
        "elapsed_s": round(best, 4),
        "tok_s": round(useful / best, 1),
        "slot_steps": slots,
        "compile": srv.compile_stats(),
    }
    if spec:
        snap = srv.registry.snapshot()
        drafted = snap["serving_spec_drafted_total"][0]["value"]
        accepted = snap["serving_spec_accepted_total"][0]["value"]
        arm["drafted"] = int(drafted)
        arm["accepted"] = int(accepted)
        arm["rejected"] = int(drafted - accepted)
        arm["acceptance_rate"] = round(accepted / max(1, drafted), 4)
    if anatomy:
        arm["profile_window"] = _anatomy_shares(srv, trace)
    srv.close()
    return arm, toks


def run_spec_section(kv):
    """The speculative off/on A/B at bs in {1, 4}: dedicated wide model
    (n_embd 512 — per-step compute dominated by streaming the weight
    matrices, the regime where skipping 7 of 8 layers for drafted
    tokens pays), tail-damped above ``draft_layers`` so the self-draft
    is representative of a trained draft's acceptance."""
    import copy

    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    k = int(os.environ.get("SERVING_BENCH_SPEC_K", "6"))
    draft_layers = int(os.environ.get("SERVING_BENCH_SPEC_LAYERS", "1"))
    damp = float(os.environ.get("SERVING_BENCH_SPEC_DAMP", "0.4"))
    gen = int(os.environ.get("SERVING_BENCH_SPEC_GEN", "96"))
    reps = int(os.environ.get("SERVING_BENCH_SPEC_REPS", "3"))
    cfg = GPT2Config(vocab_size=512, n_positions=256, n_embd=512,
                     n_layer=8, n_head=8, kv_cache_dtype=kv)
    model = GPT2LMHeadModel(cfg)
    params = jax.device_get(model.init(
        jax.random.PRNGKey(0),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"])
    params = copy.deepcopy(params)
    for i in range(draft_layers, cfg.n_layer):
        for blk, w in (("attn", "proj"), ("mlp", "proj")):
            params[f"h_{i}"][blk][w]["kernel"] = (
                params[f"h_{i}"][blk][w]["kernel"] * damp)
    eng = deepspeed_tpu.init_inference(
        model, params=jax.device_put(params), dtype=jnp.float32)

    rng = np.random.default_rng(5)

    def mk_trace(n):
        return [TraceReq(rng.integers(
            0, cfg.vocab_size,
            (int(rng.integers(8, 33)),)).astype(np.int32), gen)
            for _ in range(n)]

    runs = []
    for max_batch, n_req in ((1, 4), (4, 12)):
        trace = mk_trace(n_req)
        anatomy = max_batch == 4         # one capture pair is plenty
        off, off_toks = run_spec_arm(eng, max_batch, trace, k,
                                     draft_layers, False, reps,
                                     anatomy=anatomy)
        on, on_toks = run_spec_arm(eng, max_batch, trace, k,
                                   draft_layers, True, reps,
                                   anatomy=anatomy)
        parity = all(np.array_equal(a, b)
                     for a, b in zip(off_toks, on_toks))
        run = {
            "max_batch": max_batch,
            "n_requests": n_req,
            "useful_tokens": sum(r.gen for r in trace),
            "tok_s": {"spec_off": off["tok_s"], "spec_on": on["tok_s"]},
            "speedup": round(on["tok_s"] / off["tok_s"], 3),
            "acceptance_rate": on["acceptance_rate"],
            "drafted": on["drafted"],
            "accepted": on["accepted"],
            "rejected": on["rejected"],
            "drafted_rejected_units":
                on["slot_steps"]["units"]["drafted_rejected"],
            "greedy_parity": parity,
            "slot_steps": {"spec_off": off["slot_steps"],
                           "spec_on": on["slot_steps"]},
            "compile": {"spec_off": off["compile"],
                        "spec_on": on["compile"]},
        }
        if anatomy:
            run["profile_window"] = {
                "spec_off": off["profile_window"],
                "spec_on": on["profile_window"]}
        runs.append(run)
    return {
        "config": {
            "k": k, "draft_layers": draft_layers, "acceptance": "exact",
            "tail_damp": damp, "gen_len": gen, "reps": reps,
            "model": {"n_embd": cfg.n_embd, "n_layer": cfg.n_layer,
                      "n_positions": cfg.n_positions,
                      "vocab_size": cfg.vocab_size},
            "spec_off_decode_steps": k + 1,
        },
        "runs": runs,
    }


def run_router(eng, serving_cfg, trace, n_replicas, make_registry):
    """Aggregate throughput of ``n_replicas`` cache-armed replicas
    behind the prefix-affinity router (fresh engines per run; every
    replica warmed outside the timed window)."""
    import copy

    from deepspeed_tpu.serving.router import ServingRouter
    from deepspeed_tpu.serving.server import ServingEngine
    engines = [ServingEngine(eng, config=copy.deepcopy(serving_cfg),
                             registry=make_registry())
               for _ in range(n_replicas)]
    router = ServingRouter(engines)
    for e in engines:
        e.submit(trace[0].prompt[:9], max_new_tokens=2)
    while any(e.scheduler.has_work() for e in engines):
        router.step()
    router.collect()
    t0 = time.perf_counter()
    rids = [router.submit(r.prompt, max_new_tokens=r.gen) for r in trace]
    outs = {o.req_id: o for o in router.serve_forever()}
    elapsed = time.perf_counter() - t0
    assert set(rids) == set(outs), "router trace must fully drain"
    useful = sum(r.gen for r in trace)
    hit_rates = [e.cache.prefix_cache.stats()["hit_rate"]
                 for e in engines]
    return {
        "replicas": n_replicas,
        "elapsed_s": round(elapsed, 4),
        "aggregate_tok_s": round(useful / elapsed, 1),
        "routed_by_replica": list(router.routed_by_replica),
        "prefix_hit_rate_by_replica": hit_rates,
    }


def main():
    import jax
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel,
                                           PRESETS)
    from deepspeed_tpu.serving.server import ServingEngine
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry
    from deepspeed_tpu.utils import groups

    name = os.environ.get("SERVING_BENCH_MODEL", "bench-small")
    n_req = int(os.environ.get("SERVING_BENCH_N", "96"))
    kv = os.environ.get("SERVING_BENCH_KV", "auto")
    max_batch = int(os.environ.get("SERVING_BENCH_BATCH", "8"))
    if name == "bench-small":
        # big enough that per-step compute dominates host dispatch (the
        # regime the technique targets); small enough to regen anywhere
        cfg = GPT2Config(vocab_size=512, n_positions=192, n_embd=256,
                         n_layer=8, n_head=8, kv_cache_dtype=kv)
    else:
        import dataclasses as dc
        cfg = dc.replace(PRESETS[name], kv_cache_dtype=kv)
    groups.destroy()
    groups.initialize()
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    trace = build_trace(n_req, cfg.vocab_size, max_batch)
    max_model_len = max(len(r.prompt) + r.gen for r in trace)
    useful_tokens = sum(r.gen for r in trace)

    base_s, base_ttfts, waste = run_baseline(eng, trace, max_batch)

    registry = MetricsRegistry()
    # decode_steps=8 amortises host dispatch
    serving_cfg = {"max_batch": max_batch, "block_size": 32,
                   "prefill_chunk": 64, "max_model_len": max_model_len,
                   "decode_steps": int(os.environ.get(
                       "SERVING_BENCH_DECODE_STEPS", "8")),
                   # the slot-step ledger rides the timed run (pure host
                   # bookkeeping); SLO thresholds parked high and the
                   # snapshot parked in /tmp so a bench can never clobber
                   # the committed SERVING_HEALTH.json demo artifact
                   "observability": {
                       "enabled": True, "window": 32,
                       "ttft_slo_ms": 1e12, "preemption_thrash": 10 ** 9,
                       "no_progress_steps": 10 ** 9,
                       "trace_lanes": False,
                       "snapshot_file": os.path.join(
                           "/tmp", "serving_bench_health.json")}}
    srv, srv_s, srv_ttfts, occ, warm = run_serving(
        lambda: ServingEngine(eng, config=serving_cfg, registry=registry),
        trace)

    tok_hist = registry.histogram("serving_token_latency_ms")
    stats = srv.compile_stats()
    K = serving_cfg["decode_steps"]
    slot_steps = slot_steps_of(srv, warm, max_batch, K)
    sched_steps, total_units = slot_steps["steps"], slot_steps["total_units"]

    # ---- opt-in obs-server arm: what answering live scrapes costs
    obs_section = None
    if os.environ.get("BENCH_OBS_SERVER") == "1":
        off_s, on_s_obs, scrapes = run_obs_scraped(eng, serving_cfg,
                                                   trace)
        obs_section = {
            "scrape_interval_s": OBS_SCRAPE_INTERVAL_S,
            "scrapes": scrapes["n"],
            "scrape_errors": scrapes["errors"],
            "pairs": scrapes["pairs"],
            "elapsed_s": {"server_off": round(off_s, 4),
                          "server_on": round(on_s_obs, 4)},
            "tok_s": {"server_off": round(useful_tokens / off_s, 1),
                      "server_on": round(useful_tokens / on_s_obs, 1)},
            # fraction of throughput lost to answering scrapes
            # (interleaved A/B pairs on the same warm engine, best-of
            # per arm)
            "tok_s_delta_frac": round(
                max(0.0, 1.0 - off_s / on_s_obs), 4),
        }

    # ---- shared-prefix A/B: equal config, prefix cache off then on
    ptrace = build_prefix_trace(
        int(os.environ.get("SERVING_BENCH_PREFIX_N", "64")),
        cfg.vocab_size,
        prefix_pool=int(os.environ.get("SERVING_BENCH_PREFIX_POOL", "4")),
        prefix_len=int(os.environ.get("SERVING_BENCH_PREFIX_LEN", "96")),
        reuse_ratio=float(os.environ.get("SERVING_BENCH_REUSE", "0.9")))
    srv_off, off_s, off_ttfts, _, off_warm = run_serving(
        lambda: ServingEngine(eng, config=dict(serving_cfg),
                              registry=MetricsRegistry()), ptrace)
    off_slots = slot_steps_of(srv_off, off_warm, max_batch, K)
    shared_peak = [0]

    def sample_shared(s):
        shared_peak[0] = max(shared_peak[0],
                             s.cache.prefix_cache.shared_blocks())
    cache_cfg = {**serving_cfg, "prefix_cache": {"enabled": True}}
    srv_on, on_s, on_ttfts, _, on_warm = run_serving(
        lambda: ServingEngine(eng, config=dict(cache_cfg),
                              registry=MetricsRegistry()), ptrace,
        sample=sample_shared)
    on_slots = slot_steps_of(srv_on, on_warm, max_batch, K)
    pc_stats = srv_on.cache.prefix_cache.stats()
    off_p50 = _exact_percentile(off_ttfts, .5) * 1e3
    on_p50 = _exact_percentile(on_ttfts, .5) * 1e3
    prefix_section = {
        "trace": {
            "n_requests": len(ptrace),
            "prefix_pool": int(os.environ.get(
                "SERVING_BENCH_PREFIX_POOL", "4")),
            "prefix_len": int(os.environ.get(
                "SERVING_BENCH_PREFIX_LEN", "96")),
            "reuse_ratio": float(os.environ.get(
                "SERVING_BENCH_REUSE", "0.9")),
            "seed": 1,
        },
        "hit_rate": pc_stats["hit_rate"],
        "hits": pc_stats["hits"],
        "misses": pc_stats["misses"],
        "cow_forks": pc_stats["cow_forks"],
        "blocks_shared_peak": shared_peak[0],
        "insertions": pc_stats["insertions"],
        "ttft_p50_ms": {"cache_off": round(off_p50, 2),
                        "cache_on": round(on_p50, 2)},
        "ttft_improvement": round(off_p50 / on_p50, 3),
        "elapsed_s": {"cache_off": round(off_s, 4),
                      "cache_on": round(on_s, 4)},
        "prefill_chunks": {
            "cache_off": int(srv_off.registry.counter(
                "serving_prefill_chunks_total").value
                - off_warm["serving_prefill_chunks_total"]),
            "cache_on": int(srv_on.registry.counter(
                "serving_prefill_chunks_total").value
                - on_warm["serving_prefill_chunks_total"])},
        "slot_steps": {"cache_off": off_slots, "cache_on": on_slots},
        "compile": srv_on.compile_stats(),
    }

    # ---- router: aggregate tok/s vs replica count, same trace shape
    rtrace = build_prefix_trace(
        int(os.environ.get("SERVING_BENCH_ROUTER_N", "32")),
        cfg.vocab_size, seed=2)
    router_section = {
        "trace_requests": len(rtrace),
        "useful_tokens": sum(r.gen for r in rtrace),
        "runs": [run_router(eng, cache_cfg, rtrace, n, MetricsRegistry)
                 for n in (1, 2)],
    }

    # ---- speculative off/on A/B (dedicated bandwidth-bound model)
    spec_section = run_spec_section(kv)

    doc = {
        "schema": "deepspeed_tpu.serving_bench/4",
        "scenario": {
            "model": name, "n_embd": cfg.n_embd, "n_layer": cfg.n_layer,
            "backend": jax.default_backend(), "kv_cache": kv,
            "n_requests": n_req, "max_batch": max_batch,
            "block_size": serving_cfg["block_size"],
            "prefill_chunk": serving_cfg["prefill_chunk"],
            "max_model_len": max_model_len,
            "prompt_len_range": [int(min(len(r.prompt) for r in trace)),
                                 int(max(len(r.prompt) for r in trace))],
            "gen_len_range": [int(min(r.gen for r in trace)),
                              int(max(r.gen for r in trace))],
            "useful_tokens": useful_tokens,
        },
        "baseline": {
            "mode": "batch_synchronous_generate",
            "elapsed_s": round(base_s, 4),
            "tok_s": round(useful_tokens / base_s, 1),
            "wasted_decode_frac": round(waste, 4),
            "ttft_ms": {"p50": round(_exact_percentile(base_ttfts, .5) * 1e3, 2),
                        "p99": round(_exact_percentile(base_ttfts, .99) * 1e3, 2)},
        },
        "serving": {
            "mode": "continuous_batching_paged_kv",
            "elapsed_s": round(srv_s, 4),
            "tok_s": round(useful_tokens / srv_s, 1),
            "decode_steps": int(registry.counter(
                "serving_decode_steps_total").value
                - warm["serving_decode_steps_total"]),
            "prefill_chunks": int(registry.counter(
                "serving_prefill_chunks_total").value
                - warm["serving_prefill_chunks_total"]),
            "preemptions": int(srv.scheduler.preemptions_total),
            "ttft_ms": {"p50": round(_exact_percentile(srv_ttfts, .5) * 1e3, 2),
                        "p99": round(_exact_percentile(srv_ttfts, .99) * 1e3, 2)},
            "token_latency_ms": {
                "p50": _r(tok_hist.quantile(.5)),
                "p99": _r(tok_hist.quantile(.99))},
            "kv_occupancy": {"mean": round(float(np.mean(occ)), 4),
                             "peak": round(float(np.max(occ)), 4)},
            "slot_steps": slot_steps,
            "compile": stats,
        },
        "prefix_cache": prefix_section,
        "router": router_section,
        "speculative": spec_section,
    }
    doc["speedup"] = round(doc["serving"]["tok_s"]
                           / doc["baseline"]["tok_s"], 3)
    if obs_section is not None:
        doc["obs_server"] = obs_section

    print(json.dumps(doc, indent=2))
    if doc["serving"]["tok_s"] <= doc["baseline"]["tok_s"]:
        print("REFUSING to write artifact: continuous batching did not "
              "beat the batch-synchronous baseline on this run",
              file=sys.stderr)
        sys.exit(1)
    if stats["decode_signatures"] != 1 or stats["retraces"]:
        print("REFUSING to write artifact: decode-step program count "
              f"!= 1 ({stats})", file=sys.stderr)
        sys.exit(1)
    if not slot_steps["sums_exact"]:
        print("REFUSING to write artifact: slot-step categories sum to "
              f"{total_units} units but {sched_steps} steps x "
              f"{max_batch} slots x K={K} is "
              f"{slot_steps['expected_units']} — the by-construction "
              "invariant broke", file=sys.stderr)
        sys.exit(1)
    if slot_steps["wasted_frac"] >= doc["baseline"]["wasted_decode_frac"]:
        print("REFUSING to write artifact: serving wasted "
              f"{slot_steps['wasted_frac']:.1%} of its slot-steps, not "
              "below the static baseline's "
              f"{doc['baseline']['wasted_decode_frac']:.1%} — continuous "
              "batching stopped paying for itself", file=sys.stderr)
        sys.exit(1)
    if on_p50 >= off_p50:
        print("REFUSING to write artifact: prefix cache ON gave TTFT "
              f"p50 {on_p50:.1f} ms, not better than cache OFF's "
              f"{off_p50:.1f} ms at equal config — the cache stopped "
              "paying for itself", file=sys.stderr)
        sys.exit(1)
    for label, ss in (("cache_off", off_slots), ("cache_on", on_slots)):
        if not ss["sums_exact"]:
            print(f"REFUSING to write artifact: {label} slot-step "
                  f"categories sum to {ss['total_units']} units, "
                  f"expected {ss['expected_units']} — the "
                  "by-construction invariant broke", file=sys.stderr)
            sys.exit(1)
    if obs_section is not None and obs_section["tok_s_delta_frac"] > 0.02:
        print("REFUSING to write artifact: answering live scrapes cost "
              f"{obs_section['tok_s_delta_frac']:.1%} of serving tok/s "
              f"(over {obs_section['scrapes']} scrape(s)) — the "
              "observability plane stopped being free", file=sys.stderr)
        sys.exit(1)
    pc_compile = prefix_section["compile"]
    if pc_compile["decode_signatures"] != 1 or pc_compile["retraces"]:
        print("REFUSING to write artifact: cache-on run's decode "
              f"program count != 1 ({pc_compile})", file=sys.stderr)
        sys.exit(1)
    for run in spec_section["runs"]:
        bs = run["max_batch"]
        if run["speedup"] < 1.5:
            print("REFUSING to write artifact: speculation gave only "
                  f"{run['speedup']}x at max_batch={bs} — below the "
                  "1.5x acceptance floor at bs<=4", file=sys.stderr)
            sys.exit(1)
        if not run["greedy_parity"]:
            print("REFUSING to write artifact: speculative tokens "
                  f"diverged from the plain greedy stream at "
                  f"max_batch={bs} — lossless acceptance broke",
                  file=sys.stderr)
            sys.exit(1)
        sc = run["compile"]["spec_on"]
        if (sc.get("draft_signatures") != 1
                or sc.get("verify_signatures") != 1
                or sc["decode_signatures"] != 0 or sc["retraces"]):
            print("REFUSING to write artifact: speculative steady state "
                  f"is not exactly {{1 draft, 1 verify}} programs / 0 "
                  f"retraces at max_batch={bs} ({sc})", file=sys.stderr)
            sys.exit(1)
        for label in ("spec_off", "spec_on"):
            ss = run["slot_steps"][label]
            if not ss["sums_exact"]:
                print(f"REFUSING to write artifact: {label} slot-step "
                      f"categories sum to {ss['total_units']} units at "
                      f"max_batch={bs}, expected "
                      f"{ss['expected_units']} — the by-construction "
                      "invariant broke", file=sys.stderr)
                sys.exit(1)
    if not any(run["rejected"] > 0 for run in spec_section["runs"]):
        print("REFUSING to write artifact: no drafted token was ever "
              "rejected — the artifact must demonstrate speculation "
              "cost being booked, not a draft that never misses",
              file=sys.stderr)
        sys.exit(1)
    out = os.environ.get("SERVING_BENCH_OUT") or os.path.join(
        os.path.dirname(__file__), "..", "..", "SERVING_BENCH.json")
    with open(out, "w") as f:
        json.dump(doc, f, indent=2)
        f.write("\n")
    print(f"wrote {os.path.abspath(out)}")


if __name__ == "__main__":
    main()
