"""The program times its own step: ``trace_span`` is live whenever a JAX
profiler session is (and the shared no-op otherwise), the spans inside
``ServingEngine.step`` and ``train_batch`` nest as PERF.md §3 lists them,
and the paged-block counts equal a recount from the slots' lengths.

Counts and structure only: a CPU run yields no time worth asserting."""

import contextlib
import math
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.models.simple import SimpleModel, random_dataloader, \
    sample_batch
from deepspeed_tpu.serving.server import ServingEngine
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.telemetry.manager import TelemetryManager
from deepspeed_tpu.telemetry.metrics import MetricsRegistry
from deepspeed_tpu.telemetry.serving_observatory import ServingObservatory
from deepspeed_tpu.utils import groups


@pytest.fixture
def fresh_tracer():
    """A not-enabled tracer of the test's own as the process global."""
    mine = tracer_mod.Tracer()
    old = tracer_mod.set_tracer(mine)
    yield mine
    tracer_mod.set_tracer(old)


@contextlib.contextmanager
def profiler_session(tmp_path):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _inside(child, parent):
    return (parent["ts"] <= child["ts"] and
            child["ts"] + child["dur"] <= parent["ts"] + parent["dur"] + 1)


def _children(events, parent):
    """Events (other than ``parent``) that lie inside it, by start; ties in
    the microsecond clock keep the order the spans closed in. A garbage
    collection's span (``serving_gc``, ``train_gc``) lies under whatever
    span was open when the collector ran, so it is no part of the step's
    structure and is left out."""
    return sorted((e for e in events if e is not parent
                   and e["ph"] == "X" and not e["name"].endswith("_gc")
                   and _inside(e, parent)),
                  key=lambda e: e["ts"])


# ------------------------------------------------------------------ switch
def test_no_session_no_block_the_shared_noop_and_an_empty_list(fresh_tracer):
    assert tracer_mod.trace_span("a", req=1) is tracer_mod._NULL_SPAN
    with tracer_mod.trace_span("b") as span:
        span.set(k=1)               # the no-op takes it and keeps nothing
    fresh_tracer.instant("marker")
    fresh_tracer.emit({"name": "lane", "ph": "X", "ts": 0, "dur": 1})
    assert fresh_tracer.events() == []
    assert not fresh_tracer.live


def test_a_profiler_session_makes_spans_live_in_list_and_capture(
        fresh_tracer, tmp_path):
    with profiler_session(tmp_path):
        assert fresh_tracer.live and not fresh_tracer.enabled
        with tracer_mod.trace_span("outer_span", req=7) as span:
            with tracer_mod.trace_span("inner_span"):
                pass
            span.set(blocks_needed=3)
    assert tracer_mod.trace_span("after") is tracer_mod._NULL_SPAN
    by_name = {e["name"]: e for e in fresh_tracer.events()}
    assert set(by_name) == {"outer_span", "inner_span"}
    assert by_name["outer_span"]["args"] == {"req": 7, "blocks_needed": 3}
    assert _inside(by_name["inner_span"], by_name["outer_span"])

    from jax.profiler import ProfileData
    (capture,) = tmp_path.rglob("*.xplane.pb")
    host = [p for p in ProfileData.from_file(str(capture)).planes
            if p.name == "/host:CPU"]
    assert len(host) == 1
    found = {ev.name: (ev.start_ns, ev.duration_ns, dict(ev.stats))
             for line in host[0].lines for ev in line.events
             if ev.name in by_name}
    assert found["outer_span"][2] == {"req": 7, "blocks_needed": 3}
    assert found["inner_span"][2] == {}
    o, i = found["outer_span"], found["inner_span"]
    assert o[0] <= i[0] and i[0] + i[1] <= o[0] + o[1]


def test_an_enabled_tracer_records_without_a_session():
    tr = tracer_mod.Tracer(enabled=True)
    with tr.span("x", step=1) as span:
        span.set(more=2)
    (ev,) = tr.events()
    assert ev["args"] == {"step": 1, "more": 2}


def test_a_disabled_manager_spans_into_the_global_tracer(fresh_tracer,
                                                         tmp_path):
    manager = TelemetryManager(None)
    assert manager.tracer is None
    assert manager.span("quiet") is tracer_mod._NULL_SPAN
    with profiler_session(tmp_path):
        with manager.span("engine_side", global_step=3):
            pass
        manager.instant("marker")
    assert [e["name"] for e in fresh_tracer.events()] == ["engine_side",
                                                          "marker"]


def test_the_observatory_draws_its_lanes_by_the_same_predicate(fresh_tracer,
                                                              tmp_path):
    ob = ServingObservatory(
        max_batch=2, decode_steps=1, registry=MetricsRegistry(),
        snapshot_path=str(tmp_path / "SERVING_HEALTH.json"),
        on_escalate=lambda: None, log_fn=lambda *a: None)
    req = types.SimpleNamespace(
        req_id=1, slot=0, prompt=[1, 2, 3], max_new_tokens=8, preemptions=0,
        output_tokens=[], block_table=[], submit_t=0.0)
    ob.record_submit(req)
    ob.on_admit(req)                        # no session: draws nothing
    assert fresh_tracer.events() == []
    with profiler_session(tmp_path / "capture"):
        ob.on_preempt(req, "capacity_growth", evicted_tokens=3)
        req.preemptions = 1
        ob.on_admit(req)
    drawn = [e["name"] for e in fresh_tracer.events() if e["ph"] != "M"]
    assert drawn == ["req1 preempted", "req1 queued"]


# ----------------------------------------------------------------- serving
def _serving(speculative):
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                     n_layer=4, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    config = {"max_batch": 3, "block_size": 8, "prefill_chunk": 6}
    if speculative:
        config["speculative"] = {"enabled": True, "k": 3, "draft_layers": 2}
    return ServingEngine(eng, config=config, registry=MetricsRegistry())


@pytest.mark.parametrize("speculative", [False, True],
                         ids=["plain", "speculative"])
def test_serving_step_spans_nest_and_count(fresh_tracer, tmp_path,
                                           speculative):
    srv = _serving(speculative)
    rng = np.random.default_rng(3)
    for p_len, new in [(5, 9), (19, 6), (30, 12), (11, 4)]:
        srv.submit(rng.integers(0, 256, (p_len,)).astype(np.int32),
                   max_new_tokens=new)
    BS, B = srv.cache.block_size, srv.max_batch
    chunks = srv.registry.counter("serving_prefill_chunks_total")
    decodes = srv.registry.counter("serving_decode_steps_total")
    needed_c = srv.registry.counter("serving_paged_blocks_needed_total")
    visited_c = srv.registry.counter("serving_paged_blocks_visited_total")

    lengths = []            # per decode dispatch: the decoding slots' lengths
    run_decode = srv._run_decode
    generated = srv.registry.counter("serving_tokens_generated_total")

    def recording(decode_slots):
        lengths.append([srv.scheduler.slots[i].cached_len
                        for i in decode_slots])
        return run_decode(decode_slots)

    srv._run_decode = recording
    # what each step added: chunks, decode dispatches, landings, tokens
    per_step = []
    with profiler_session(tmp_path):
        for _ in range(200):
            c0, d0, g0, n0 = (chunks.value, decodes.value, generated.value,
                              len(lengths))
            srv.step()
            per_step.append((int(chunks.value - c0), len(lengths) - n0,
                             int(decodes.value - d0),
                             int(generated.value - g0)))
            if not srv.scheduler.num_active and not srv.scheduler.num_waiting:
                break
    assert len(srv.collect()) == 4

    events = fresh_tracer.events()
    steps = sorted((e for e in events if e["name"] == "serving_step"),
                   key=lambda e: e["ts"])
    assert len(steps) == len(per_step)
    recount = iter(lengths)
    total_needed = total_visited = 0
    in_flight = 0           # slots of the dispatch that has not landed
    for step, (n_chunks, n_dispatches, n_landings, n_tokens) in zip(
            steps, per_step):
        inside = _children(events, step)
        names = [e["name"] for e in inside]
        assert names.count("serving_schedule") == 1
        assert names.count("serving_publish") == 1
        assert names.count("serving_prefill") == n_chunks
        assert names.count("serving_decode") == n_dispatches
        assert names.count("serving_decode_wait") == n_landings
        assert names.count("serving_deliver") == n_landings
        assert names[0] == "serving_schedule"
        assert names[-1] == "serving_publish"
        # a step runs ahead where it dispatched a decode with the tokens of
        # the step before still in flight; under speculation it never does
        assert step["args"]["ahead"] == int(
            bool(n_dispatches) and bool(in_flight) and not speculative)
        for e in inside:
            if e["name"] == "serving_prefill":
                assert {"req", "start", "tokens"} <= set(e["args"])
        for decode in (e for e in inside if e["name"] == "serving_decode"):
            # the step's own dispatch closes BEFORE the wait opens, and
            # that wait is for the dispatch of the step before (for this
            # step's own under speculation: there the two are one)
            inner = _children(events, decode)
            landed_here = speculative or in_flight
            assert [e["name"] for e in inner] == [
                "serving_decode_inputs", "serving_decode_dispatch"] + (
                ["serving_decode_wait", "serving_deliver"]
                if landed_here else [])
            if landed_here:
                dispatch, wait = inner[1], inner[2]
                assert dispatch["ts"] + dispatch["dur"] <= wait["ts"]
            lens = np.asarray(next(recount))
            args = decode["args"]
            assert args["batch"] == len(lens)
            assert args["blocks_needed"] == int(np.ceil(lens / BS).sum())
            assert args["blocks_visited"] == B * math.ceil(lens.max() / BS)
            total_needed += args["blocks_needed"]
            total_visited += args["blocks_visited"]
            if speculative:
                continue
            # the wait's tokens are step k-1's: one for each slot that
            # step dispatched (greedy, no EOS), none of this step's own
            assert n_tokens == in_flight
            in_flight = args["batch"]
        if not n_dispatches and not speculative:
            # nothing to dispatch (every request's last token in flight):
            # the step lands what is left, directly under itself
            assert n_landings == int(bool(in_flight))
            assert n_tokens == in_flight
            in_flight = 0
    assert not in_flight
    for e in events:
        if e["name"] == "serving_gc":
            assert e["args"]["generation"] in (0, 1, 2)
            assert "collected" in e["args"]
    assert sum(n for n, *_ in per_step) > 4     # the prompts took chunks
    assert (needed_c.value, visited_c.value) == (total_needed, total_visited)
    assert 0 < total_needed <= total_visited


def test_the_paged_block_counters_move_with_no_session(fresh_tracer):
    srv = _serving(False)
    srv.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
    list(srv.serve_forever())
    # prompt 19: decodes at lengths 18, 19, 20 -> 3 blocks of 8 each time,
    # the loop visits max_batch x 3
    assert srv.registry.counter(
        "serving_paged_blocks_needed_total").value == 9
    assert srv.registry.counter(
        "serving_paged_blocks_visited_total").value == 27
    assert fresh_tracer.events() == []


# ---------------------------------------------------------------- training
@pytest.mark.parametrize("gas,want", [
    (1, ["train_input", "fused_step", "train_post"]),
    (2, ["train_input", "train_input", "train_post"])],
    ids=["fused", "accumulating"])
def test_train_batch_spans_with_telemetry_off(fresh_tracer, tmp_path, gas,
                                              want):
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=32, nlayers=2),
        config={"train_batch_size": 16 * gas,
                "train_micro_batch_size_per_gpu": 2,
                "gradient_accumulation_steps": gas,
                "steps_per_print": 10 ** 9,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}},
        sample_batch=sample_batch(2, 32), seed=42)
    assert not engine.telemetry.enabled
    it = iter(random_dataloader(engine, total_samples=64 * gas,
                                hidden_dim=32, seed=0))
    engine.train_batch(data_iter=it)        # compiles, untraced
    assert fresh_tracer.events() == []
    with profiler_session(tmp_path):
        engine.train_batch(data_iter=it)
        engine.train_batch(data_iter=it)
    events = fresh_tracer.events()
    batches = sorted((e for e in events if e["name"] == "train_batch"),
                     key=lambda e: e["ts"])
    assert [b["args"]["global_step"] for b in batches] == [1, 2]
    known = {"train_input", "fused_step", "train_place", "train_dispatch",
             "train_post"}
    for batch in batches:
        inside = [e for e in _children(events, batch) if e["name"] in known]
        top = [e for e in inside
               if e["name"] not in ("train_place", "train_dispatch")]
        assert [e["name"] for e in top] == want
        if gas == 1:
            (fused,) = [e for e in inside if e["name"] == "fused_step"]
            assert [e["name"] for e in _children(events, fused)
                    if e["name"] in known] == ["train_place",
                                               "train_dispatch"]
            # the four phases of PERF.md §3, in order
            assert [e["name"] for e in inside if e is not fused] == [
                "train_input", "train_place", "train_dispatch", "train_post"]
