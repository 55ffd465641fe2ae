"""Traffic kind ``serve-closed``: ``init_inference`` -> ``init_serving`` ->
``submit`` / ``step`` / ``collect`` under a closed loop.

The runner keeps the scheduler's queue primed the way ``serve_forever``
does, so a freed slot is refilled at the next step and the schedule depends
on counts, never on the clock. The fill-up — from an empty engine until
each of the first ``max_batch`` requests has its first token — belongs to
set-up and ends on that count; the window opens and closes on step
boundaries. What the window's own requests produced is what ``correct``
compares with the plain reference, once the window has closed, the peak has
been read and the engine is freed. Every request that was served a token is
compared, not a sample: greedy tokens tell a precision from the next one
down only where two logits nearly tie, which some thousands of tokens show
and some hundreds do not (PERF.md, Findings).
"""

import gc
import time

import numpy as np

from benchmark import harness, traffic as traffic_gen


def _rows(sample, width):
    """Each checked request as one right-padded row of prompt + served
    tokens, and the token chosen AT each position (the next one)."""
    ids = np.zeros((len(sample), width), np.int32)
    for r, (prompt, tokens) in enumerate(sample):
        ids[r, :len(prompt)] = prompt
        ids[r, len(prompt):len(prompt) + len(tokens)] = tokens
    return ids, np.roll(ids, -1, axis=1)


def _served_positions(sample):
    for r, (prompt, tokens) in enumerate(sample):
        yield r, slice(len(prompt) - 1, len(prompt) - 1 + len(tokens))


def numbers(cell, seed, sample, quant=False) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token (greedy traffic), and the
    mean gap: the widest swings by its nature, the mean is steadier from
    seed to seed. ``quant`` reads the CONTROL instead: at each position of
    the same prompts and tokens, the gap of the token that the lower
    precision puts first."""
    ref = harness.load_named("reference", cell.config["reference"])
    width = max(p + o for p, o in traffic_gen.request_shapes(cell.traffic))
    ids, picks = _rows(sample, width)
    if quant:
        _, _, picks = ref.teacher_forced(cell.config, seed, ids, picks,
                                         quant=True)
    top, picked, _ = ref.teacher_forced(cell.config, seed, ids, picks)
    gaps = np.concatenate([(top - picked)[r, where]
                           for r, where in _served_positions(sample)])
    return {"top_gap_max": float(gaps.max()),
            "top_gap_mean": float(gaps.mean())}


def run(cell, seed, seconds, tracer, spans, compiles) -> dict:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    ref = harness.load_named("reference", cell.config["reference"])
    program = harness.load_named("programs", cell.config["reference"])
    tr, V = cell.traffic, cell.config["vocab_size"]
    devices = jax.devices()[:cell.chips]
    groups.destroy()
    groups.initialize(devices=devices)

    dtype = getattr(jnp, cell.config["precision"])
    with spans("setup.engine"):
        engine = deepspeed_tpu.init_inference(
            program.model(cell.config), dtype=dtype,
            params=ref.make_weights(ref.seed_words(seed),
                                    ref.sizes(cell.config), dtype))
        serving = {**cell.config["deployment"]["serving"], **tr["serving"]}
        srv = deepspeed_tpu.init_serving(engine=engine,
                                         config={"serving": serving})
    max_batch = srv.max_batch
    shapes = traffic_gen.request_shapes(tr)
    depth = int(tr["queue_depth_in_batches"]) * max_batch
    chunks = srv.registry.counter("serving_prefill_chunks_total")
    generated = srv.registry.counter("serving_tokens_generated_total")

    sent = {}           # req_id -> (index, prompt ids, output length)
    done = {}           # req_id -> RequestOutput

    def top_up():
        while srv.scheduler.num_waiting < depth:
            i = len(sent)
            p_len, o_len = shapes[i % len(shapes)]
            prompt = traffic_gen.prompt_ids(seed, i, p_len, V)
            rid = srv.submit(prompt, max_new_tokens=o_len,
                             temperature=float(tr["temperature"]),
                             eos_token_id=None)
            sent[rid] = (i, prompt, o_len)

    def progress():
        """Output tokens delivered so far, by request: finished and
        unfinished alike."""
        out = {rid: len(o.tokens) for rid, o in done.items()}
        out.update({r.req_id: len(r.output_tokens)
                    for r in srv.scheduler.slots if r is not None})
        return out

    def step():
        c0, g0, t0 = chunks.value, generated.value, time.perf_counter()
        with spans("top_up"):
            top_up()
        with spans("step"):
            srv.step()
        with spans("collect"):
            for o in srv.collect():
                done[o.req_id] = o
        return (t0, time.perf_counter(), int(chunks.value - c0),
                int(generated.value - g0))

    # ---- fill-up (set-up): ends on a count, never on the clock
    schedule = []
    with spans("setup.fill_up"):
        while True:
            schedule.append(step()[2:])
            have = progress()
            if all(have.get(rid, 0) >= 1 for rid in range(max_batch)):
                break
        jax.block_until_ready(srv.pools)

    # ---- the measured window, opened and closed on step boundaries
    compiles_before, stats_before = compiles.count, srv.compile_stats()
    steps = []
    with tracer.window(devices, settle=lambda: (step(), step())):
        done_at_open = set(done)
        at_open, t_open = progress(), time.perf_counter()
        while True:
            steps.append(step())
            if steps[-1][1] - t_open >= seconds:
                break
        jax.block_until_ready(srv.pools)
        t_close, at_close = time.perf_counter(), progress()
    window_s = t_close - t_open
    delivered = sum(at_close.values()) - sum(at_open.values())
    peak = harness.memory_peak_bytes(devices)
    compiled = (compiles.count - compiles_before) + (
        srv.compile_stats()["retraces"] - stats_before["retraces"])

    finished = [rid for rid in done if rid not in done_at_open]
    wrong = [rid for rid in finished
             if done[rid].finish_reason != "max_tokens"
             or len(done[rid].tokens) != sent[rid][2]
             or list(done[rid].prompt) != sent[rid][1].tolist()
             or not all(0 <= t < V for t in done[rid].tokens)]

    # what is checked: every request that was served a token by the close,
    # finished or not (an unfinished one is the prefix of its greedy answer)
    slots = {r.req_id: r for r in srv.scheduler.slots if r is not None}
    served = {rid: list(o.tokens) for rid, o in done.items()}
    served.update({rid: list(r.output_tokens) for rid, r in slots.items()})
    sample = [(sent[rid][1], np.asarray(tokens, np.int32))
              for rid, tokens in sorted(served.items()) if tokens]
    shape_of = {rid: (len(p), o) for rid, (_, p, o) in sent.items()}

    srv.close()
    del srv, engine
    groups.destroy()
    gc.collect()

    with spans("check.reference"):
        compared = numbers(cell, seed, sample)
    return {
        "attempted": len(finished), "failed": len(wrong),
        "end_to_end": {"serve_tokens_per_s":
                       (delivered / window_s, "tokens/s")},
        "memory_peak_bytes": peak,
        "numbers": {**compared, "served_wrong": float(len(wrong))},
        "evidence": sample,
        "records": {"kind": "serve-closed", "steps": steps,
                    "window_s": window_s, "delivered": delivered,
                    "max_batch": max_batch, "fill_up": schedule,
                    "at_open": at_open, "at_close": at_close,
                    "shape_of": shape_of,
                    "compiles_in_window": compiled},
        "devices": devices,
    }
