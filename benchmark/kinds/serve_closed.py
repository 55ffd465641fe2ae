"""Traffic kind ``serve-closed``: ``init_inference`` -> ``init_serving`` ->
``submit`` / ``step`` / ``collect`` under a closed loop.

The runner keeps the scheduler's queue primed the way ``serve_forever``
does, so a freed slot is refilled at the next step and the schedule depends
on counts, never on the clock. The fill-up — from an empty engine until
each of the first ``max_batch`` requests has its first token — belongs to
set-up and ends on that count; the window opens and closes on step
boundaries. A traced run's window is a few seconds, so it first steps on,
untraced and uncounted, until each of those first requests has FINISHED:
the slots then hold the steady state's mix of lengths and prefill chunks,
which the seconds right after the fill-up do not, and the traced window
describes what the untraced rate measures.

What the requests produced is what ``correct`` compares with the plain
reference, once the window has closed, the peak has been read and the
engine is freed: of the requests that were served a token in the window,
``check_requests_per_slot`` from EVERY slot, spread over the window from
its open to its close, the longest request among them
(:func:`check_sample`). The number is the traffic file's and does not
follow the rate: a maximum over more tokens reads higher, so limits set at
one rate would fail sound runs at a higher one, and the reference's time
would grow with every gain. It is some thousands of tokens: greedy tokens
tell a precision from the next one down only where two logits nearly tie,
which some thousands of tokens show and some hundreds do not (PERF.md,
Findings).
"""

import gc
import time

import numpy as np

from benchmark import harness, traffic as traffic_gen

ROW_WIDTH = 128


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


def check_sample(seed, served: dict, slot_of: dict, per_slot: int) -> list:
    """Request ids out of ``served`` (id -> (prompt, tokens)): ``per_slot``
    from each slot that ``slot_of`` (id -> slot) names, all of a slot's where
    it served no more. A slot's requests are in the order of their ids,
    which is the order it served them in; the picks lie at evenly spaced
    places of that order, and the places shift from slot to slot (the slots
    in an order drawn from the seed) so that together they lie evenly from
    the window's open to its close: the first slot gives the request it held
    at the open, the last one the request it holds at the close. The longest
    request of all is always in."""
    by_slot = {}
    for rid in sorted(served):
        by_slot.setdefault(slot_of[rid], []).append(rid)
    rng = np.random.default_rng([int(seed), 1 << 40])   # no prompt's stream
    slots = [sorted(by_slot)[i] for i in rng.permutation(len(by_slot))]
    shift = rng.random()
    picked = {max(sorted(served),
                  key=lambda rid: sum(len(x) for x in served[rid]))}
    for rank, slot in enumerate(slots):
        rids = by_slot[slot]
        place = (rank + shift) / len(slots)             # in [0, 1)
        picked.update(rids if len(rids) <= per_slot else
                      (rids[int((j + place) * len(rids) / per_slot)]
                       for j in range(per_slot)))
    return sorted(picked)


def numbers(cell, seed, sample, quant=False) -> dict:
    """The widest gap by which a served token's reference logit lies below
    the reference's best, over every served token of the sample (greedy
    traffic), and the mean gap: the widest swings by its nature, the mean is
    steadier from seed to seed. ``quant`` reads the CONTROL instead: at each
    position of the same prompts and tokens, the gap of the token that the
    lower precision puts first. The rows go to the reference in groups of
    like length, each padded to the next multiple of ``ROW_WIDTH``: most
    requests are a third of the longest, and a row costs its width."""
    ref = harness.load_named("reference", cell.config["reference"])
    groups = {}
    for prompt, tokens in sample:
        width = min(-(-(len(prompt) + len(tokens)) // ROW_WIDTH) * ROW_WIDTH,
                    cell.config["n_positions"])
        groups.setdefault(width, []).append((prompt, tokens))
    gaps = []
    for width, rows in sorted(groups.items()):
        ids, picks = _rows(rows, width)
        if quant:
            _, _, picks = ref.teacher_forced(cell.config, seed, ids, picks,
                                             quant=True)
        top, picked, _ = ref.teacher_forced(cell.config, seed, ids, picks)
        gaps += [(top - picked)[r, where]
                 for r, where in _served_positions(rows)]
    gaps = np.concatenate(gaps)
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
    per_slot = int(tr["check_requests_per_slot"])
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
    lived = {}          # req_id -> [slot, first step seen in it, last step]
    stepped = [0]

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
            for slot, r in enumerate(srv.scheduler.slots):
                if r is not None and r.req_id not in lived:
                    lived[r.req_id] = [slot, stepped[0], None]
            for o in srv.collect():
                done[o.req_id] = o
                lived[o.req_id][2] = stepped[0]
            stepped[0] += 1
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

    # ---- a traced window is short: reach the steady state before it
    if tracer.on:
        with spans("setup.steady"):
            while not all(rid in done for rid in range(max_batch)):
                step()
            jax.block_until_ready(srv.pools)

    # ---- the measured window, opened and closed on step boundaries
    compiles_before, stats_before = compiles.count, srv.compile_stats()
    steps = []
    with tracer.window(devices, settle=lambda: (step(), step())):
        done_at_open, first_step = set(done), stepped[0]
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

    # what is checked: a sample of the requests that the window served a
    # token, finished or not (an unfinished one is the prefix of its greedy
    # answer), each with every token it was served
    served = {rid: list(o.tokens) for rid, o in done.items()
              if rid not in done_at_open}
    served.update({r.req_id: list(r.output_tokens)
                   for r in srv.scheduler.slots if r is not None})
    served = {rid: (sent[rid][1], np.asarray(tokens, np.int32))
              for rid, tokens in served.items() if tokens}
    slot_of = {rid: slot for rid, (slot, _, _) in lived.items()}
    picked = check_sample(seed, served, slot_of, per_slot)
    sample = [served[rid] for rid in picked]
    in_steps = set()
    for rid in picked:
        _, first, last = lived[rid]
        in_steps.update(range(max(first, first_step),
                              stepped[0] if last is None else last + 1))
    coverage = {
        "requests": len(picked),
        "tokens": sum(len(tokens) for _, tokens in sample),
        "slots_share": len({slot_of[rid] for rid in picked})
        / len({slot_of[rid] for rid in served}),
        "steps_share": len(in_steps) / len(steps)}
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
                    "sample": coverage,
                    "at_open": at_open, "at_close": at_close,
                    "shape_of": shape_of,
                    "compiles_in_window": compiled},
        "devices": devices,
    }
