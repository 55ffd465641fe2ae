"""The readers of the serving step's dispatched work and of the host's
garbage collections (``serve_dispatched_mfu``, ``offline_host_gc_ms``,
``train_host_gc_ms``) on hand-made spans, and the identity that makes the
first a count of the same work as the readers that charge delivered
tokens: a tiny serve run through the program's own spans, every request
served whole, charges exactly sum(f(p, 0, o)) for each of the three block
kinds. A CPU run yields counts, never a speed: the times here are
written, not taken."""

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, harness, program_spans

import benchmark_tiny

W0, W1 = 100.0, 110.0           # the window, seconds on perf_counter
PEAK = 197e12
HERE = Path(__file__).parent


def ev(name, start_ms, dur_ms, **args):
    """A complete Chrome-trace event ``start_ms`` after the window opens."""
    return {"name": name, "ph": "X", "ts": int(W0 * 1e6 + start_ms * 1e3),
            "dur": int(dur_ms * 1e3), "pid": 1, "tid": 1, "args": args}


def ctx(monkeypatch, events, cell=None):
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    return {"spans": [("window", W0, W1)],
            "cell": cell or benchmark_tiny.cell("tiny-backlog"),
            "records": {"window_s": W1 - W0},
            "device_kind": "TPU v5 lite"}


# ------------------------------------------------------ serve_dispatched_mfu
DISPATCHED = [ev("serving_step", 0, 9),
              ev("serving_prefill", 1, 1, req=0, start=0, tokens=8,
                 recompute=0),
              ev("serving_prefill", 2, 1, req=1, start=8, tokens=8,
                 recompute=3),
              ev("serving_decode", 3, 5, batch=4, rows=4, positions=100)]


def test_the_dispatched_share_charges_chunks_and_rows(monkeypatch):
    config = benchmark_tiny.TINY["config"]

    def f(*args):
        return flops.serve_flops(config, *args)
    need = (f(8, 0, 1) - f(0, 0, 1)) + (f(16, 0, 1) - f(11, 0, 1)) \
        + 4 * f(0, 1, 2) + 100 * (f(1, 1, 2) - f(0, 1, 2))
    # by hand: a token at position t is 2 N_blk + 4 L E t, a row's head 2 V E
    blk, per_t, head = 2 * 12 * 2 * 64 ** 2, 4 * 2 * 64, 2 * 8192 * 64
    assert need == 8 * blk + per_t * 28 + 5 * blk + per_t * 65 \
        + 4 * (blk + head) + 100 * per_t
    got = harness.load_reader("serve_dispatched_mfu")(
        ctx(monkeypatch, DISPATCHED))
    assert got == pytest.approx(100 * need / 10.0 / PEAK)


def test_the_count_is_the_cells_own():
    reader = harness.load_named("metrics", "serve_dispatched_mfu")
    granite = json.loads((HERE / "tiny_granite_4_0_h.json").read_text())
    dots = json.loads((HERE / "tiny_dots_vlm1.json").read_text())
    from benchmark import flops_mla_moe
    from benchmark.reference import granite_4_0_h
    assert reader.count_of(granite["config"]) is granite_4_0_h.serve_flops
    assert reader.count_of(dots["config"]) is flops_mla_moe.serve_flops
    assert reader.count_of(benchmark_tiny.TINY["config"]) is \
        flops.serve_flops


def _without(events, key):
    return [dict(e, args={k: v for k, v in e["args"].items() if k != key})
            for e in events]


@pytest.mark.parametrize("why", ["no-rows", "no-positions", "no-recompute",
                                 "no-decode", "speculation"])
def test_nothing_dispatched_to_read(monkeypatch, why):
    events, cell = DISPATCHED, None
    if why.startswith("no-") and why != "no-decode":
        events = _without(DISPATCHED, why[3:])
    if why == "no-decode":
        events = DISPATCHED[:3]
    if why == "speculation":
        base = benchmark_tiny.cell("tiny-backlog")
        cell = dataclasses.replace(base, traffic={
            **base.traffic, "serving": {**base.traffic["serving"],
                                        "speculative": {"enabled": True}}})
    got = harness.load_reader("serve_dispatched_mfu")(
        ctx(monkeypatch, events, cell))
    assert got is None


# ------------------------------------------ offline_host_gc_ms, train_...
@pytest.mark.parametrize("name,loop,step", [
    ("offline_host_gc_ms", "serving", "serving_step"),
    ("train_host_gc_ms", "train", "train_batch")])
def test_the_collections_a_step(monkeypatch, name, loop, step):
    events = [ev(step, 0, 10), ev(f"{loop}_gc", 2, 2, generation=0),
              ev(step, 20, 10), ev(f"{loop}_gc", 21, 0.5, generation=2),
              ev(step, 40, 10), ev(step, 60, 10),
              ev(f"{loop}_gc", 10_000, 50, generation=2)]    # after close
    read = harness.load_reader(name)
    assert read(ctx(monkeypatch, events)) == pytest.approx(2.5 / 4)
    quiet = [e for e in events if e["name"] == step]
    assert read(ctx(monkeypatch, quiet)) == 0.0


@pytest.mark.parametrize("name,step", [("offline_host_gc_ms", "serving_step"),
                                       ("train_host_gc_ms", "train_batch")])
def test_no_collection_to_read(monkeypatch, name, step):
    read = harness.load_reader(name)
    assert read(ctx(monkeypatch, [])) is None
    from deepspeed_tpu.telemetry import tracer
    monkeypatch.delattr(tracer, "watch_gc")     # a program without the hook
    assert read(ctx(monkeypatch, [ev(step, 0, 10)])) is None


def test_the_manifest_names_the_new_metrics():
    entries = {m["name"]: m for m in harness.load_manifest()["per_layer"]}
    serve = ["gpt2-medium.serve.offline", "gpt2-xl.serve.offline",
             "dots.vlm1.inst.serve.documents",
             "granite-4.0-h-micro.serve.offline"]
    assert entries["serve_dispatched_mfu"] == {
        "name": "serve_dispatched_mfu", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "serve step",
        "moves": "serve_tokens_per_s", "workloads": serve}
    assert entries["offline_host_gc_ms"] == {
        "name": "offline_host_gc_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "serve engine",
        "moves": "serve_tokens_per_s", "workloads": serve}
    assert entries["train_host_gc_ms"] == {
        "name": "train_host_gc_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "train engine",
        "moves": "train_tokens_per_s",
        "workloads": ["gpt2-medium.train.seq1024"]}


# ---------------------------------------------------------------- identity
KINDS = {"gpt2": ("tiny.json", "tiny-backlog", {}),
         "gpt2-two-rows": ("tiny.json", "tiny-backlog", {"decode_steps": 2}),
         # 10 blocks of 8 for four slots of up to 6 blocks: preemptions, and
         # chunks that re-prefill what an eviction threw away
         "gpt2-preempted": ("tiny.json", "tiny-backlog", {"num_blocks": 11}),
         "latent": ("tiny_dots_vlm1.json", "tiny-documents", {}),
         "hybrid": ("tiny_granite_4_0_h.json", "tiny-backlog", {})}
# (prompt, output): a one-token prompt (no chunk), prompts over several
# chunks, more requests than the four slots so that slots are reused
SHAPES = [(1, 3), (5, 4), (17, 6), (40, 2), (9, 5), (24, 7), (33, 3)]


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_dispatched_charges_sum_to_the_requests_counts(kind):
    import deepspeed_tpu
    from deepspeed_tpu.telemetry import Tracer, get_tracer, set_tracer
    from deepspeed_tpu.utils import groups
    file, traffic, more = KINDS[kind]
    tiny = json.loads((HERE / file).read_text())
    config = {**tiny["config"], "precision": "float32"}
    ref = harness.load_named("reference", config["reference"])
    program = harness.load_named("programs", config["reference"])
    groups.destroy()
    groups.initialize(devices=jax.devices()[:1])
    engine = deepspeed_tpu.init_inference(
        program.model(config), dtype=jnp.float32,
        params=ref.make_weights(ref.seed_words(7), ref.sizes(config),
                                jnp.float32))
    srv = deepspeed_tpu.init_serving(engine=engine, config={"serving": {
        **config["deployment"]["serving"],
        **tiny["traffic"][traffic]["serving"], **more}})
    rng = np.random.default_rng(3)
    old = set_tracer(Tracer(enabled=True))
    try:
        for p, o in SHAPES:
            srv.submit(rng.integers(0, config["vocab_size"], (p,)),
                       max_new_tokens=o)
        outs = list(srv.serve_forever())
        events = get_tracer().events()
    finally:
        set_tracer(old)
        srv.close()
    assert sorted(len(o.tokens) for o in outs) == sorted(o for _, o in SHAPES)
    top = program_spans.Span("run", 0.0, float("inf"), {},
                             program_spans.nest(events, 0.0, float("inf")))
    prefills, decodes = top.find("serving_prefill"), top.find("serving_decode")
    recomputed = sum(s.args["recompute"] for s in prefills)
    assert (recomputed > 0) == ("num_blocks" in more)
    assert sum(s.args["tokens"] for s in prefills) - recomputed == sum(
        p - 1 for p, _ in SHAPES)
    assert sum(s.args["rows"] for s in decodes) == sum(o for _, o in SHAPES)
    reader = harness.load_named("metrics", "serve_dispatched_mfu")
    f = reader.count_of(config)
    assert reader.dispatched_flops(config, prefills, decodes) == sum(
        f(config, p, 0, o) for p, o in SHAPES)
