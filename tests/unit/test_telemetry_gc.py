"""The collection hook of ``telemetry/tracer.py``: each Python garbage
collection is booked in the registering loop's counters always, and is a
span ``<loop>_gc`` under whatever span was open while the tracer is live;
engines register their loop when built and leave when closed, and the hook
is installed once."""

import gc

import jax
import jax.numpy as jnp
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
from deepspeed_tpu.models.simple import SimpleModel, sample_batch
from deepspeed_tpu.telemetry import (MetricsRegistry, Tracer, get_registry,
                                     set_tracer, trace_span)
from deepspeed_tpu.telemetry import tracer as tracer_mod
from deepspeed_tpu.utils import groups


class Owner:
    """Something a registration can belong to."""


def hooks():
    return [cb for cb in gc.callbacks if isinstance(cb, tracer_mod._GCWatch)]


def value(registry, name, generation=None):
    rows = registry.snapshot()[name]
    if generation is None:
        (row,) = rows
    else:
        (row,) = [r for r in rows if r["labels"] == {"generation":
                                                     str(generation)}]
    return row["value"]


@pytest.fixture
def live():
    old = set_tracer(Tracer(enabled=True))
    yield tracer_mod.get_tracer()
    set_tracer(old)


@pytest.fixture
def quiet():
    old = set_tracer(Tracer(enabled=False))
    yield tracer_mod.get_tracer()
    set_tracer(old)


def test_a_forced_collection_is_a_child_of_the_open_span(live):
    registry, owner = MetricsRegistry(), Owner()
    handle = tracer_mod.watch_gc("serving", registry, owner)
    try:
        with trace_span("serving_decode_wait"):
            gc.collect()
    finally:
        handle.close()
    events = live.events()
    (wait,) = [e for e in events if e["name"] == "serving_decode_wait"]
    full = [e for e in events if e["name"] == "serving_gc"
            and e["args"]["generation"] == 2]
    assert len(full) == 1
    (span,) = full
    assert set(span["args"]) == {"generation", "collected"}
    assert span["tid"] == wait["tid"]
    assert wait["ts"] <= span["ts"]
    assert span["ts"] + span["dur"] <= wait["ts"] + wait["dur"]
    assert value(registry, "serving_gc_collections_total", 2) == 1
    assert value(registry, "serving_gc_seconds_total", 2) > 0


def test_nothing_is_recorded_with_the_tracer_off_but_the_counters_count(
        quiet):
    registry, owner = MetricsRegistry(), Owner()
    handle = tracer_mod.watch_gc("train", registry, owner)
    try:
        gc.collect()
        gc.collect()
    finally:
        handle.close()
    assert quiet.events() == []
    assert value(registry, "train_gc_collections_total", 2) == 2
    paused = value(registry, "train_gc_seconds_total", 2)
    longest = value(registry, "train_gc_pause_max_seconds")
    assert 0 < longest <= paused
    gc.collect()                    # closed: booked nowhere
    assert value(registry, "train_gc_collections_total", 2) == 2


def test_the_newest_loop_owns_the_collections_and_a_dropped_owner_leaves(
        quiet):
    older, newer = MetricsRegistry(), MetricsRegistry()
    kept = Owner()
    tracer_mod.watch_gc("train", older, kept)
    dropped = Owner()
    tracer_mod.watch_gc("serving", newer, dropped)
    gc.collect()
    assert value(newer, "serving_gc_collections_total", 2) == 1
    assert value(older, "train_gc_collections_total", 2) == 0
    del dropped                     # no close(): its finalizer unregisters
    gc.collect()
    assert value(newer, "serving_gc_collections_total", 2) == 1
    assert value(older, "train_gc_collections_total", 2) == 1
    del kept
    assert len(hooks()) == 1


def test_twenty_serving_engines_leave_one_hook_and_no_registration():
    groups.initialize(devices=jax.devices()[:1])
    cfg = GPT2Config(vocab_size=128, n_positions=32, n_embd=32, n_layer=1,
                     n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0), {
        "input_ids": jnp.zeros((1, 4), jnp.int32)})["params"]
    inf = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    made = []
    for _ in range(20):
        srv = deepspeed_tpu.init_serving(engine=inf, config={"serving": {
            "max_batch": 2, "block_size": 8}})
        assert tracer_mod._GC_WATCH._loops[-1] is srv._gc
        assert srv._gc.span_name == "serving_gc"
        made.append(srv._gc)
        srv.close()
    assert len(hooks()) == 1
    assert not [h for h in tracer_mod._GC_WATCH._loops if h in made]


def test_the_train_engine_registers_its_loop_until_closed(quiet):
    engine, *_ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=16, nlayers=1),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-2}}},
        sample_batch=sample_batch(8, 16))
    assert tracer_mod._GC_WATCH._loops[-1] is engine._gc
    assert engine._gc.span_name == "train_gc"
    before = value(get_registry(), "train_gc_collections_total", 2)
    gc.collect()
    assert value(get_registry(), "train_gc_collections_total", 2) \
        == before + 1
    engine.close()
    assert all(h is not engine._gc for h in tracer_mod._GC_WATCH._loops)
    assert len(hooks()) == 1
