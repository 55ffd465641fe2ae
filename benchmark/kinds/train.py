"""Traffic kind ``train``: ``deepspeed_tpu.initialize`` -> ``train_batch``.

Set-up builds ONE engine, drives it from the seed through its first
``compared_steps`` optimizer steps (the first compiles, all go through the
window's own call and feed) and hands that same engine to the window. What
those steps produced is what ``correct`` compares with the plain reference,
once the window has closed, the peak has been read and the engine is freed.
"""

import gc
import time

import numpy as np

from benchmark import harness, traffic as traffic_gen

GRADIENT_FLOOR = 1e-3   # leaves whose reference gradient is under this share
                        # of the median leaf's move by round-off alone under
                        # Adam: left out of the change, by this rule only


def numbers(got: dict, want: dict) -> dict:
    """The numbers compared, program (``got``) against reference
    (``want``): each step's loss, the first gradient's norm and the
    parameters' change after the compared steps, both by the worst leaf —
    the gap between the two norms over the reference's norm of that leaf or
    of the median leaf, whichever is larger. Gaps of norms are blind to
    rounding (unbiased noise moves a norm in the second order only), so the
    first gradient is also compared by how far it points apart from the
    reference's: the norm of the difference over the reference's norm, on a
    sample of each leaf, by the median leaf."""
    out = {}
    for k, (a, b) in enumerate(zip(got["losses"], want["losses"]), 1):
        out[f"loss_gap_step{k}"] = abs(a - b) / abs(b)

    def worst(a, b, keep=None):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        gap = np.abs(a - b) / np.maximum(b, np.median(b))
        if keep is not None:
            gap = gap[keep]
        return float(gap.max())

    out["grad_norm_gap"] = worst(got["grad_norms"], want["grad_norms"])
    g = np.asarray(want["grad_norms"], np.float64)
    keep = g >= GRADIENT_FLOOR * np.median(g)
    a, b = (np.asarray(d["grad_samples"], np.float64) for d in (got, want))
    apart = np.linalg.norm(a - b, axis=1)[keep] / np.linalg.norm(b, axis=1)[keep]
    out["grad_apart_median"] = float(np.median(apart))
    out["change_norm_gap"] = worst(got["change_norms"], want["change_norms"],
                                   keep)
    return out


def reference_readings(cell, seed, quant=False, rows=None) -> dict:
    """The plain reference over the compared steps. ``quant`` computes it in
    the control's precision; ``rows`` keeps only those rows of every batch
    (a planted fault: half of the batch left out)."""
    ref = harness.load_named("reference", cell.config["reference"])
    tr = cell.traffic
    r = ref.TrainReference(cell.config, tr["engine"]["optimizer"]["params"],
                           seed, quant=quant)
    losses, grad_norms = [], None
    for k in range(int(tr["compared_steps"])):
        ids = traffic_gen.train_batch_ids(seed, k, tr["global_batch"],
                                          tr["seq_len"],
                                          cell.config["vocab_size"])
        loss, gn = r.step(ids if rows is None else ids[rows])
        losses.append(float(loss))
        if k == 0:
            grad_norms, grad_samples = (np.asarray(x) for x in gn)
    return {"losses": losses, "grad_norms": grad_norms,
            "grad_samples": grad_samples,
            "change_norms": np.asarray(r.change_norms())}


def run(cell, seed, seconds, tracer, spans, compiles) -> dict:
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.utils import groups

    ref = harness.load_named("reference", cell.config["reference"])
    program = harness.load_named("programs", cell.config["reference"])
    tr = cell.traffic
    B, S, V = tr["global_batch"], tr["seq_len"], cell.config["vocab_size"]
    devices = jax.devices()[:cell.chips]
    mesh = tr["mesh"]
    groups.destroy()
    groups.initialize(mp_size=mesh["mp"], devices=devices)

    sz = ref.sizes(cell.config)
    words = ref.seed_words(seed)
    with spans("setup.engine"):
        engine, _, _, _ = deepspeed_tpu.initialize(
            model=program.model(cell.config), config=dict(tr["engine"]),
            model_parameters=ref.make_weights(words, sz, jnp.float32),
            mp_rules=(program.tensor_parallel_rules() if mesh["mp"] > 1
                      else None))

    def feed():
        step = 0
        while True:
            yield {"input_ids": traffic_gen.train_batch_ids(seed, step, B, S, V)}
            step += 1

    batches = feed()
    b1 = tr["engine"]["optimizer"]["params"].get("betas", (0.9, 0.999))[0]
    got = {"losses": []}
    with spans("setup.first_steps"):
        for k in range(int(tr["compared_steps"])):
            got["losses"].append(float(engine.train_batch(data_iter=batches)))
            if k == 0:      # Adam's first moment after one step is (1-b1)·g
                got["grad_norms"] = np.asarray(
                    ref.leaf_norms(engine.state.opt_state.mu)) / (1.0 - b1)
                got["grad_samples"] = np.asarray(
                    ref.leaf_samples(engine.state.opt_state.mu)) / (1.0 - b1)
        got["change_norms"] = np.asarray(ref.leaf_norms_of_difference(
            engine.state.params, ref.make_weights(words, sz, jnp.float32)))
        jax.block_until_ready(engine.state.params)

    # ---- the measured window: every step counted, a fresh batch each
    compiles_before = compiles.count
    steps, losses, pending = [], [], None
    with tracer.window(devices, settle=lambda: float(
            engine.train_batch(data_iter=batches))):
        t_open = time.perf_counter()
        while True:
            t0 = time.perf_counter()
            with spans("step"):
                loss = engine.train_batch(data_iter=batches)
                if tracer.on:
                    loss.block_until_ready()     # per-step times, synced
                elif pending is not None:
                    pending.block_until_ready()  # run one step ahead, no more
            pending = loss
            losses.append(loss)
            now = time.perf_counter()
            steps.append((t0, now))
            if now - t_open >= seconds:
                break
        loss.block_until_ready()
        t_close = time.perf_counter()
    window_s = t_close - t_open
    compiled = compiles.count - compiles_before
    peak = harness.memory_peak_bytes(devices)
    finite = np.isfinite(np.asarray(jax.device_get(losses), np.float64))

    engine.close()
    del engine, loss, pending, losses
    groups.destroy()
    gc.collect()

    with spans("check.reference"):
        want = reference_readings(cell, seed)
    return {
        "attempted": len(steps), "failed": int((~finite).sum()),
        "end_to_end": {"train_tokens_per_s":
                       (len(steps) * B * S / window_s, "tokens/s")},
        "memory_peak_bytes": peak,
        "numbers": numbers(got, want), "evidence": got, "reference": want,
        "records": {"kind": "train", "steps": steps, "window_s": window_s,
                    "tokens_per_step": B * S, "seq_len": S,
                    "global_batch": B,
                    "compiles_in_window": compiled},
        "devices": devices,
    }
