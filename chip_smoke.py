"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py            # gpt2-medium, full width and depth
    python chip_smoke.py --kernels  # every Pallas kernel vs its jnp reference
    python chip_smoke.py --tiny     # toy size for the CPU tier-1 test

One process, every device jax finds, the entry points a user calls:

* train — ``deepspeed_tpu.initialize`` -> 5 x ``train_batch`` on one fixed
  synthetic batch (global 8 x 1024, Adam, ZeRO-1, bf16). With more than one
  device the same job runs first on one device, then on all of them as
  dp=N ZeRO-1 and as dp=N/2 x mp=2 ZeRO-3 at the same global batch and seed.
* serve — ``init_inference`` -> ``init_serving`` (default ``serving`` block)
  -> 8 greedy requests drained by ``serve_forever``, compared token by token
  with ``InferenceEngine.generate`` (the Pallas decode kernel) and scored
  against a teacher-forced ``mha_reference`` forward.

A phase that raises fails the run; nothing here catches a phase. Without an
accelerator the script exits non-zero before printing a result — the toy
size is chosen by ``--tiny`` only, never by which devices were found. Times
printed here are smoke readings from one run, not benchmark results. The
last line of stdout is ``{"ok": true, "device": {...}}``.
"""

import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from importlib.metadata import version

import numpy as np

# ---- stated tolerances (bf16; set from the PR-21 v5e runs, see PERF.md) ----
LOSS0_BAND = 0.5            # |step-0 loss - ln(vocab)|
PARITY_ROWS = 4             # batch rows of the flash-vs-reference check
FLASH_LOSS_RTOL = 5e-4      # flash vs mha_reference, same params and rows
FLASH_GNORM_RTOL = 5e-3     # (measured 1.1e-5 and 6.2e-5)
LAYOUT_LOSS0_ATOL = 5e-3    # step-0 loss, N-device layout vs one device
                            # (measured <= 2e-4)
SHARD_SHARE_SLACK = 1.4     # a device holds <= slack/N of a sharded state
BYTES_IN_USE_RATIO = 1.5    # most- vs least-loaded device
SERVE_TOP_GAP = 0.10        # reference logit: max minus the chosen token
                            # (measured <= 0.044)
SERVE_MIN_AGREE = 0.5       # share of tokens equal to generate's, up to
                            # each request's first difference (measured 0.72)
# max|got-want| / max|want| per kernel output (measured <= 9.6e-3 / 2.7e-6)
KERNEL_TOL = {"bfloat16": 2e-2, "float32": 5e-5}


@dataclasses.dataclass(frozen=True)
class Size:
    cfg: object              # GPT2Config
    batch: int
    seq: int
    prompt_lens: tuple
    new_tokens: int
    zero3_persist: object    # stage3_param_persistence_threshold or None


def _sizes():
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Config
    full = Size(PRESETS["gpt2-medium"], 8, 1024,
                (64, 100, 160, 224, 288, 352, 448, 512), 32, None)
    # the toy's leaves are all under the default persistence threshold,
    # which would keep ZeRO-3 params replicated and void the shard check
    # (use_flash: the CPU would otherwise pick XLA attention and skip the
    # interpreted Pallas kernels and their mesh wrapper.)
    tiny = Size(GPT2Config(vocab_size=512, n_positions=128, n_embd=64,
                           n_layer=2, n_head=4, use_flash=True),
                8, 64, (8, 12, 16, 20, 24, 32, 40, 48), 8, 0)
    return full, tiny


def _mem(devices):
    """Per-device bytes_in_use / peak_bytes_in_use (None where the
    backend keeps no allocator statistics, i.e. the CPU)."""
    out = []
    for d in devices:
        s = d.memory_stats() or {}
        out.append({"id": d.id, "bytes_in_use": s.get("bytes_in_use"),
                    "peak_bytes_in_use": s.get("peak_bytes_in_use")})
    return out


def _shard_bytes(tree, devices):
    """Bytes of ``tree`` each device holds in its addressable shards, and
    the bytes of one full copy."""
    import jax
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        total += leaf.nbytes
        for sh in leaf.addressable_shards:
            held[sh.device.id] += sh.data.nbytes
    return held, total


def _check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ---------------------------------------------------------------- train
def _flash_parity(size, params, batch):
    """Loss and gradient norm of the model on ``params`` and the first
    ``PARITY_ROWS`` rows of ``batch``, through the Pallas flash kernel and
    through ``mha_reference``. Both remat their blocks and take half the
    batch: beside the engine's own state, the reference's [B,H,S,S] fp32
    scores and the [B,S,V] logits of the full batch would crowd the chip."""
    import jax
    import jax.numpy as jnp
    import optax
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

    batch = jax.tree.map(lambda x: x[:PARITY_ROWS], batch)
    out = {"rows": PARITY_ROWS}
    for name, use_flash in (("flash", True), ("reference", False)):
        model = GPT2LMHeadModel(dataclasses.replace(
            size.cfg, use_flash=use_flash, remat=True))

        def loss_and_gnorm(p):
            loss, g = jax.value_and_grad(lambda q: model.apply(
                {"params": jax.tree.map(
                    lambda x: x.astype(jnp.bfloat16), q)}, batch))(p)
            return loss, optax.global_norm(g)

        loss, gnorm = jax.jit(loss_and_gnorm)(params)
        out[name] = {"loss": float(loss), "grad_norm": float(gnorm)}
    f, r = out["flash"], out["reference"]
    out["loss_rel_diff"] = abs(f["loss"] - r["loss"]) / abs(r["loss"])
    out["grad_norm_rel_diff"] = (abs(f["grad_norm"] - r["grad_norm"])
                                 / abs(r["grad_norm"]))
    _check(out["loss_rel_diff"] <= FLASH_LOSS_RTOL
           and out["grad_norm_rel_diff"] <= FLASH_GNORM_RTOL,
           f"flash vs mha_reference disagree beyond bf16 tolerance "
           f"(loss rtol {FLASH_LOSS_RTOL}, grad-norm rtol "
           f"{FLASH_GNORM_RTOL}): {out}")
    return out


def train_phase(size, devices, *, zero_stage, mp_size, on_chip, parity):
    """One training job: 5 steps on one fixed batch under the given
    layout. Returns the record printed in the summary."""
    import jax
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2LMHeadModel, gpt2_tp_rules,
                                           synthetic_batch)
    from deepspeed_tpu.ops import _platform
    from deepspeed_tpu.runtime.zero.partition import ModelParallelRules
    from deepspeed_tpu.utils import groups
    from deepspeed_tpu.utils.chip import CHECKOUT

    n = len(devices)
    dp = n // mp_size
    groups.destroy()
    groups.initialize(mp_size=mp_size, devices=devices)
    zero = {"stage": zero_stage}
    if zero_stage == 3 and size.zero3_persist is not None:
        zero["stage3_param_persistence_threshold"] = size.zero3_persist
    config = {
        "train_batch_size": size.batch,
        "train_micro_batch_size_per_gpu": size.batch // dp,
        "steps_per_print": 10 ** 9,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
        "zero_optimization": zero,
        "bf16": {"enabled": True},
        # the cost explorer keeps the compiled step, whose HLO is read below
        "telemetry": {"enabled": True, "trace": False, "jsonl": False,
                      "prometheus": False,
                      "output_path": str(CHECKOUT / "telemetry"),
                      "job_name": "chip_smoke",
                      "cost_explorer": {"enabled": True}},
    }
    batch = synthetic_batch(size.batch, size.seq, size.cfg.vocab_size, seed=1)
    t0 = time.perf_counter()
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(size.cfg), config=config,
        sample_batch=synthetic_batch(size.batch, size.seq,
                                     size.cfg.vocab_size, seed=0),
        mp_rules=(ModelParallelRules(gpt2_tp_rules())
                  if mp_size > 1 else None))
    rec = {"layout": f"dp={dp} mp={mp_size} zero={zero_stage}",
           "devices": n, "init_s": round(time.perf_counter() - t0, 2)}
    if parity:
        rec["flash_vs_reference"] = _flash_parity(
            size, engine.state.params, batch)

    losses, step_s = [], []
    for _ in range(5):
        t0 = time.perf_counter()
        losses.append(float(engine.train_batch(batch=batch)))  # float() syncs
        step_s.append(time.perf_counter() - t0)
    rec["losses"] = [round(x, 4) for x in losses]
    rec["first_step_s"] = round(step_s[0], 2)        # compile + one step
    rec["steady_step_ms"] = round(min(step_s[1:]) * 1e3, 1)
    rec["cold_compile_s"] = round(step_s[0] - min(step_s[1:]), 2)

    ln_v = math.log(size.cfg.vocab_size)
    _check(all(math.isfinite(x) for x in losses), f"non-finite loss: {losses}")
    _check(abs(losses[0] - ln_v) <= LOSS0_BAND,
           f"step-0 loss {losses[0]:.3f} not within {LOSS0_BAND} of "
           f"ln(vocab) = {ln_v:.3f}")
    _check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    if on_chip:
        hlo = engine._aot_step_for("fused_train_step").compiled.as_text()
        rec["tpu_custom_calls"] = hlo.count('custom_call_target="tpu_custom_call"')
        _check(not _platform.interpret(),
               "ops/_platform.interpret() is True on the chip")
        _check(rec["tpu_custom_calls"] > 0,
               "compiled train step holds no tpu_custom_call: flash "
               "attention did not lower through Mosaic")

    if n > 1:
        rec["collectives"] = dict(
            engine.get_cost_census().collective_counts)
        # ZeRO-1 shards the optimizer state, ZeRO-3 the params as well
        sharded = {"opt_state": engine.state.opt_state}
        if zero_stage == 3:
            sharded["params"] = engine.state.params
        for what, tree in sharded.items():
            held, total = _shard_bytes(tree, devices)
            share = {i: round(b / total, 4) for i, b in held.items()}
            rec[f"{what}_bytes_per_device"] = held
            rec[f"{what}_share_per_device"] = share
            _check(max(share.values()) <= SHARD_SHARE_SLACK / n,
                   f"{what} is not sharded {n} ways: per-device share of "
                   f"one copy {share} (want about {1 / n:.3f})")
    rec["memory"] = _mem(devices)
    in_use = [m["bytes_in_use"] for m in rec["memory"]]
    if n > 1 and all(b for b in in_use):
        rec["bytes_in_use_max_over_min"] = round(max(in_use) / min(in_use), 3)
        _check(max(in_use) <= BYTES_IN_USE_RATIO * min(in_use),
               f"device memory is unbalanced: bytes_in_use {in_use}")

    engine.close()
    del engine
    groups.destroy()
    gc.collect()
    return rec


# ---------------------------------------------------------------- serve
def serve_phase(size, device, seed=0):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    from deepspeed_tpu.utils import groups

    cfg = size.cfg
    groups.destroy()
    groups.initialize(devices=[device])       # serving drives one chip
    model = GPT2LMHeadModel(cfg)
    params = jax.jit(model.init)(
        jax.random.PRNGKey(seed),
        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    inf = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.bfloat16)
    del params
    srv = deepspeed_tpu.init_serving(engine=inf)    # default serving block

    rng = np.random.default_rng(seed + 1)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in size.prompt_lens]
    new = size.new_tokens

    # warm-up: one short request compiles the prefill and decode programs
    t0 = time.perf_counter()
    srv.submit(prompts[0][:8], max_new_tokens=2)
    srv.serve_forever()
    rec = {"cold_compile_s": round(time.perf_counter() - t0, 2)}

    t0 = time.perf_counter()
    rids = [srv.submit(p, max_new_tokens=new) for p in prompts]
    outs = {o.req_id: o for o in srv.serve_forever()}
    wall = time.perf_counter() - t0
    served = [outs[r].tokens for r in rids]
    rec["requests"] = len(rids)
    rec["prompt_lens"] = list(size.prompt_lens)
    rec["drain_s"] = round(wall, 3)
    rec["ms_per_generated_token"] = round(wall * 1e3 / (len(rids) * new), 3)
    rec["compile_stats"] = srv.compile_stats()
    _check(all(len(t) == new and all(0 <= x < cfg.vocab_size for x in t)
               for t in served),
           f"a request did not return {new} in-vocabulary tokens: "
           f"{[len(t) for t in served]}")
    _check(rec["compile_stats"] == {"decode_signatures": 1,
                                    "prefill_signatures": 1, "retraces": 0},
           f"serving compiled more than one program per entry point: "
           f"{rec['compile_stats']}")

    # the same prompts through InferenceEngine.generate (one compile per
    # prompt length; on the chip its steps run the Pallas decode kernel)
    t0 = time.perf_counter()
    generated = []
    for p in prompts:
        out = inf.generate(jnp.asarray(p)[None], max_new_tokens=new)
        generated.append(np.asarray(out)[0, len(p):].tolist())
    rec["generate_s"] = round(time.perf_counter() - t0, 2)

    # Teacher-forced reference: one mha_reference forward over every
    # (prompt + output) row, right-padded to one length (causal, so the
    # pad changes nothing before it). For the token chosen at each step
    # it gives the reference logit and the reference maximum.
    ref = GPT2LMHeadModel(dataclasses.replace(cfg, use_flash=False))
    width = max(size.prompt_lens) + new

    @jax.jit
    def score(p, ids, picks):
        logits = ref.apply({"params": p}, {"input_ids": ids},
                           return_logits=True)[..., :cfg.vocab_size]
        chosen = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
        return logits.max(-1), chosen

    def rows(outputs):
        ids = np.zeros((len(prompts), width), np.int32)
        for r, (p, t) in enumerate(zip(prompts, outputs)):
            ids[r, :len(p)] = p
            ids[r, len(p):len(p) + new] = t
        return ids

    def top_gaps(outputs, picks=None):
        """max-minus-chosen reference logit at every generated position;
        ``picks`` overrides which token is read (default: the output)."""
        ids = rows(outputs)
        nxt = np.roll(ids, -1, axis=1)          # token chosen AT position i
        if picks is not None:
            nxt = picks(nxt)
        with inf.mesh:
            top, chosen = score(inf.params, jnp.asarray(ids),
                                jnp.asarray(nxt))
        top, chosen = np.asarray(top), np.asarray(chosen)
        return [(top - chosen)[r, len(p) - 1:len(p) - 1 + new]
                for r, p in enumerate(prompts)]

    g_srv, g_gen = top_gaps(served), top_gaps(generated)
    _check(all(np.isfinite(g).all() for g in g_srv + g_gen),
           "non-finite reference logits for a generated token")
    rec["max_top_gap_served"] = round(float(max(g.max() for g in g_srv)), 4)
    rec["max_top_gap_generated"] = round(
        float(max(g.max() for g in g_gen)), 4)

    # agreement with generate, and the logit gap where the two part ways
    first = [next((i for i in range(new) if s[i] != g[i]), None)
             for s, g in zip(served, generated)]
    rec["first_difference"] = first
    rec["agreeing_share"] = round(
        sum(new if f is None else f for f in first) / (len(first) * new), 4)

    def swap_in_generates(nxt):
        for r, (p, f) in enumerate(zip(prompts, first)):
            if f is not None:
                nxt[r, len(p) - 1 + f] = generated[r][f]
        return nxt

    g_alt = top_gaps(served, picks=swap_in_generates)
    rec["logit_gap_at_first_difference"] = [
        None if f is None else round(float(abs(g_alt[r][f] - g_srv[r][f])), 4)
        for r, f in enumerate(first)]
    worst = max(rec["max_top_gap_served"], rec["max_top_gap_generated"])
    _check(worst <= SERVE_TOP_GAP,
           f"a greedy token sits {worst} below the reference argmax "
           f"(bound {SERVE_TOP_GAP}): serving or generate disagrees with "
           f"mha_reference beyond bf16")
    _check(rec["agreeing_share"] >= SERVE_MIN_AGREE,
           f"serving and generate agree on {rec['agreeing_share']} of the "
           f"tokens before their first difference (floor {SERVE_MIN_AGREE})")
    rec["memory"] = _mem([device])
    srv.close()
    groups.destroy()
    return rec


# -------------------------------------------------------------- kernels
def _kernel_cases():
    """(name, dtype, thunk) per Pallas kernel at a production shape; the
    thunk returns [(label, got, want), ...] against the jnp reference."""
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.ops.transformer.attention import mha_reference

    def rnd(shape, seed, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(jax.random.PRNGKey(seed), shape,
                                  jnp.float32) * scale).astype(dtype)

    def attn_case(S, H, fused, ref):
        """fwd + bwd of an attention kernel vs its reference on
        [1, H, S, 64] bf16 (the per-(batch, head) program is the
        production one; H only bounds the reference's [H,S,S] scores)."""
        q, k, v, w = (rnd((1, H, S, 64), i, jnp.bfloat16) for i in range(4))

        def run(fn):
            def loss(q, k, v):
                o = fn(q, k, v)
                return jnp.sum(o.astype(jnp.float32)
                               * w.astype(jnp.float32)), o
            (_, o), g = jax.jit(jax.value_and_grad(
                loss, (0, 1, 2), has_aux=True))(q, k, v)
            return (o,) + g

        return list(zip(("out", "dq", "dk", "dv"), run(fused), run(ref)))

    def flash(S, H):
        from deepspeed_tpu.ops.transformer.flash import flash_attention
        return lambda: attn_case(
            S, H, lambda q, k, v: flash_attention(q, k, v, True, None),
            lambda q, k, v: mha_reference(q, k, v, causal=True))

    def sparse(S, H, block, local, impl):
        def thunk():
            from deepspeed_tpu.ops.sparse_attention import fused_kernels, kernels
            from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
                FixedSparsityConfig
            layout = FixedSparsityConfig(
                num_heads=H, block=block, num_local_blocks=local,
                num_global_blocks=1).make_layout(S)
            mask = jnp.asarray(kernels.layout_to_dense_mask(
                layout, block, S))[None]
            fn = (fused_kernels.block_sparse_attention_fused
                  if impl == "fused" else kernels.block_sparse_attention)
            return attn_case(
                S, H, lambda q, k, v: fn(q, k, v, layout, block=block),
                lambda q, k, v: mha_reference(q, k, v, causal=False,
                                              mask=mask))
        return thunk

    def decode(int8):
        def thunk():
            from deepspeed_tpu.ops.transformer import decode as dec
            B, H, T, D = 8, 16, 1024, 64
            q = rnd((B, H, 1, D), 0, jnp.bfloat16)
            k, v = rnd((B, H, T, D), 1, jnp.bfloat16), \
                rnd((B, H, T, D), 2, jnp.bfloat16)
            lens = jnp.asarray([1, 17, 128, 300, 512, 513, 900, 1024],
                               jnp.int32)
            if not int8:
                f = lambda uf: jax.jit(lambda: dec.decode_attention(
                    q, k, v, lens, use_flash=uf))()
            else:
                (kq, ks), (vq, vs) = dec.quantize_kv(k), dec.quantize_kv(v)
                f = lambda uf: jax.jit(
                    lambda: dec.decode_attention_quantized(
                        q, kq, ks, vq, vs, lens, use_flash=uf))()
            return [("out", f(True), f(False))]
        return thunk

    def paged_decode():
        """The server's decode walk at gpt2-xl's row (25 heads in 1,664
        lanes), ragged lengths: the kernel against the jnp walk at one
        query a slot, which on the chip rounds nothing the kernel keeps."""
        from deepspeed_tpu.serving import paged_attention as pa
        H, D, W, BS, N = 25, 64, 1664, 16, 513
        lens = np.array([0, 1, 16, 17, 150, 333, 640, 0], np.int32)
        bt = np.zeros((len(lens), 64), np.int32)
        blocks = iter(np.random.default_rng(0).permutation(np.arange(1, N)))
        for b, n in enumerate(lens):
            for i in range(-(-int(n) // BS)):
                bt[b, i] = next(blocks)
        lanes = (jnp.arange(W) < H * D).astype(jnp.bfloat16)
        k_pool, v_pool = (rnd((2 * N, BS, W), i, jnp.bfloat16) * lanes
                          for i in (0, 1))
        q, k_cur, v_cur = (rnd((len(lens), H, D), i, jnp.bfloat16)
                           for i in (2, 3, 4))
        args = (q, k_cur, v_cur, N, k_pool, v_pool, jnp.asarray(bt),
                jnp.asarray(lens))
        return [("out", pa._decode_kernel_call(*args, D ** -0.5),
                 jax.jit(lambda q, k, v, *a: pa.paged_chunk_attention(
                     q[:, :, None], k[:, :, None], v[:, :, None],
                     *a)[:, :, 0])(*args))]

    def latent_decode():
        """The same kernel over one latent a token (128 heads' dense
        queries, rows of 576 in 640 lanes whose first 512 are the
        values), ragged lengths, against the jnp walk at one query a
        slot; both hand the probabilities to the MXU in bfloat16."""
        from deepspeed_tpu.serving import paged_attention as pa
        H, Wq, V, W, BS, N = 128, 576, 512, 640, 16, 513
        lens = np.array([0, 1, 16, 17, 150, 333, 2000, 0], np.int32)
        bt = np.zeros((len(lens), 128), np.int32)
        blocks = iter(np.random.default_rng(0).permutation(np.arange(1, N)))
        for b, n in enumerate(lens):
            for i in range(-(-int(n) // BS)):
                bt[b, i] = next(blocks)
        lanes = (jnp.arange(W) < Wq).astype(jnp.bfloat16)
        pool = rnd((2 * N, BS, W), 0, jnp.bfloat16) * lanes
        q = rnd((len(lens), H, Wq), 1, jnp.bfloat16, 0.2)
        row = rnd((len(lens), Wq), 2, jnp.bfloat16)
        args = (N, pool, None, jnp.asarray(bt), jnp.asarray(lens))
        return [("out", pa._decode_kernel_call(q, row, None, *args,
                                               Wq ** -0.5, v_width=V),
                 jax.jit(lambda q, row, first, pool, bt, lens:
                         pa.paged_chunk_attention(
                             q[:, :, None], row[:, None], None, first, pool,
                             None, bt, lens, v_width=V)[:, :, 0])(
                                 q, row, N, pool, *args[3:]))]

    def grouped_experts():
        """The held experts' grouped product (megablox ``gmm`` at the
        served tiles): 16 experts of the published width, a decode step's
        handful of rows an expert and an empty one, against a loop over
        the experts."""
        from deepspeed_tpu.moe.held_experts import grouped_matmul
        G, K, M = 16, 7168, 4096
        sizes = np.array([4, 0, 9, 1, 3, 7, 2, 5, 4, 4, 130, 6, 3, 2, 8, 4],
                         np.int32)
        rows = rnd((1024, K), 0, jnp.bfloat16)
        w = rnd((G, K, M), 1, jnp.bfloat16, 0.02)
        got = grouped_matmul(rows, w, jnp.asarray(sizes), jnp.float32)
        ends = np.cumsum(sizes)
        want = jnp.concatenate([
            jnp.dot(rows[e - n:e], w[g], preferred_element_type=jnp.float32)
            for g, (e, n) in enumerate(zip(ends, sizes)) if n])
        return [("out", got[:int(ends[-1])], want)]

    def layer_norm():
        from deepspeed_tpu.ops.transformer.fused import fused_layer_norm
        x, g, b = rnd((8192, 1024), 0), rnd((1024,), 1) + 1.0, rnd((1024,), 2)

        def ref(x, g, b):
            mu = jnp.mean(x, -1, keepdims=True)
            var = jnp.var(x, -1, keepdims=True)
            return (x - mu) * jax.lax.rsqrt(var + 1e-5) * g + b

        def run(fn):
            y, grads = jax.jit(jax.value_and_grad(
                lambda x, g, b: jnp.sum(fn(x, g, b) ** 2), (0, 1, 2)))(x, g, b)
            return (jax.jit(fn)(x, g, b),) + grads

        return list(zip(("y", "dx", "dgamma", "dbeta"),
                        run(fused_layer_norm), run(ref)))

    def bias_gelu():
        from deepspeed_tpu.ops.transformer.fused import fused_bias_gelu
        x, b = rnd((8192, 4096), 0), rnd((4096,), 1)
        ref = lambda x, b: jax.nn.gelu(x + b, approximate=True)

        def run(fn):
            grads = jax.jit(jax.grad(
                lambda x, b: jnp.sum(fn(x, b) ** 2), (0, 1)))(x, b)
            return (jax.jit(fn)(x, b),) + grads

        return list(zip(("y", "dx", "dbias"), run(fused_bias_gelu),
                        run(ref)))

    def softmax():
        from deepspeed_tpu.ops.transformer.fused import fused_softmax
        x = rnd((2, 16, 1024, 1024), 0)
        return [("y", jax.jit(lambda x: fused_softmax(x, scale=0.125))(x),
                 jax.nn.softmax(x * 0.125, axis=-1))]

    def optimizer(make_fused, make_ref):
        def thunk():
            params = {"w": rnd((1024, 4096), 0), "b": rnd((4096,), 1)}
            grads = {"w": rnd((1024, 4096), 2), "b": rnd((4096,), 3)}
            got, want = [], []
            for make, acc in ((make_fused, got), (make_ref, want)):
                opt = make(weight_decay=0.01)
                u, _ = jax.jit(opt.update)(grads, opt.init(params), params,
                                           jnp.float32(1e-3))
                acc.extend([u["w"], u["b"]])
            return list(zip(("update_w", "update_b"), got, want))
        return thunk

    def adam_sweep():
        from deepspeed_tpu.ops.adam.fused_adam import (adam_sweep_apply,
                                                       sweep_pad)
        n = 128 * sweep_pad()                        # 4M elements
        p, g, m = rnd((n,), 0), rnd((n,), 1), rnd((n,), 2)
        v = jnp.abs(rnd((n,), 3))
        u, m2, v2, cast = jax.jit(lambda *a: adam_sweep_apply(
            *a, 1e-3, 0.9, 0.99, 1.0, weight_decay=0.01,
            cast_dtype=jnp.bfloat16, use_pallas=True))(p, g, m, v)
        mr = 0.9 * m + 0.1 * g
        vr = 0.999 * v + 0.001 * g * g
        ur = -1e-3 * (mr / 0.9) / (jnp.sqrt(vr / 0.99) + 1e-8) \
            - 1e-3 * 0.01 * p
        return [("u", u, ur), ("m", m2, mr), ("v", v2, vr),
                ("cast", cast, (p + ur).astype(jnp.bfloat16))]

    def quantizer(stochastic):
        def thunk():
            from deepspeed_tpu.ops.quantizer import quantizer as qz
            x = rnd((1024, 4096), 0)
            y = jax.jit(lambda x: qz.quantize(
                x, num_bits=8, groups=1024, stochastic=stochastic,
                seed=7))(x)
            if not stochastic:
                return [("y", y, qz._quantize_rows(x, 8, True, False, None))]
            # stochastic rounding draws from the TPU PRNG: no bitwise
            # reference, but every value stays within one level of x and
            # the rounding is unbiased (plain floor would average -0.5)
            step = jnp.max(jnp.abs(x), -1, keepdims=True) / 127.0
            bias = jnp.mean((y - x) / step)
            return [("|y-x|<=step", jnp.maximum(jnp.abs(y - x) - step, 0.0)
                     + 1.0, jnp.ones_like(x)),
                    ("|mean (y-x)/step|<0.01",
                     (jnp.abs(bias) < 0.01).astype(jnp.float32),
                     jnp.ones(()))]
        return thunk

    from deepspeed_tpu.ops.adam.fused_adam import fused_adam
    from deepspeed_tpu.ops.lamb.fused_lamb import fused_lamb
    from deepspeed_tpu.runtime import optim as optim_lib
    return [
        ("flash resident fwd+bwd seq 1024", "bfloat16", flash(1024, 16)),
        ("flash streaming fwd+bwd seq 8192", "bfloat16", flash(8192, 4)),
        ("block-sparse fused fwd+bwd seq 8192 block 128", "bfloat16",
         sparse(8192, 4, 128, 8, "fused")),
        ("block-sparse predicated fwd+bwd seq 2048 block 64", "bfloat16",
         sparse(2048, 16, 64, 4, "predicated")),
        ("decode attention bf16 cache", "bfloat16", decode(False)),
        ("decode attention int8 cache", "bfloat16", decode(True)),
        ("paged decode walk, 25 heads in 1,664 lanes", "float32",
         paged_decode),
        ("paged decode walk, one latent a token (128 heads, 640 lanes)",
         "bfloat16", latent_decode),
        ("grouped expert product, 16 experts of 7,168 x 4,096", "bfloat16",
         grouped_experts),
        ("fused layer norm fwd+bwd", "float32", layer_norm),
        ("fused bias-gelu fwd+bwd", "float32", bias_gelu),
        ("fused softmax", "float32", softmax),
        ("fused Adam (per tensor)", "float32",
         optimizer(fused_adam, optim_lib.adam)),
        ("fused Adam (one sweep)", "float32", adam_sweep),
        ("fused LAMB", "float32", optimizer(fused_lamb, optim_lib.lamb)),
        ("quantizer int8 symmetric", "float32", quantizer(False)),
        ("quantizer int8 stochastic", "float32", quantizer(True)),
    ]


def kernels_mode(summary):
    """Compile every Pallas kernel once, non-interpreted, and compare it
    with its reference. Unlike the default run this catches per kernel —
    the table must list EVERY refusal in one call — and the run fails if
    any row is not ``compiled``."""
    import jax
    from deepspeed_tpu.ops import _platform
    _check(not _platform.interpret(),
           "--kernels compiles through Mosaic: it needs the TPU")
    table = []
    for name, dtype, thunk in _kernel_cases():
        row = {"kernel": name, "dtype": dtype}
        t0 = time.perf_counter()
        try:
            pairs = thunk()
            errs = {}
            for label, got, want in pairs:
                got = np.asarray(jax.device_get(got), np.float32)
                want = np.asarray(jax.device_get(want), np.float32)
                errs[label] = float(np.max(np.abs(got - want))
                                    / (np.max(np.abs(want)) + 1e-30))
            row["rel_max_err"] = {k: float(f"{v:.3g}")
                                  for k, v in errs.items()}
            ok = all(np.isfinite(v) and v <= KERNEL_TOL[dtype]
                     for v in errs.values())
            row["status"] = "compiled" if ok else "mismatch"
        except Exception as e:  # noqa: BLE001 — the message IS the result
            row["status"] = "refused"
            row["message"] = f"{type(e).__name__}: {e}"
        row["seconds"] = round(time.perf_counter() - t0, 1)
        table.append(row)
        msg = row.get("message", "").replace("\n", " ")[:300]
        print(f"# {row['status']:9s} {name}  "
              f"{row.get('rel_max_err', '')} {msg}", flush=True)
        gc.collect()
    summary["kernels"] = table
    return all(r["status"] == "compiled" for r in table)


# ------------------------------------------------------------------ main
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tiny", action="store_true",
                    help="toy model for the CPU tier-1 test (the only way "
                         "to run without an accelerator)")
    ap.add_argument("--kernels", action="store_true",
                    help="compile every Pallas kernel against its jnp "
                         "reference instead of the train/serve run")
    args = ap.parse_args(argv)

    from deepspeed_tpu.utils.chip import (CHECKOUT, device_info,
                                          enable_compile_cache,
                                          require_accelerator)
    cache_dir = enable_compile_cache()
    import jax
    import jaxlib
    device = device_info() if args.tiny else require_accelerator()
    on_chip = not args.tiny
    if on_chip:
        _check(device["platform"] == "tpu",
               f"expected platform tpu, found {device['platform']}")
    summary = {"device": device, "size": "tiny" if args.tiny else "full",
               "versions": {"jax": jax.__version__,
                            "jaxlib": jaxlib.__version__,
                            "libtpu": version("libtpu")},
               "compile_cache_dir": cache_dir,
               "note": "times are smoke readings from one run, "
                       "not benchmark results"}
    print(f"# chip_smoke: {json.dumps(summary)}", flush=True)

    t_start = time.perf_counter()
    if args.kernels:
        ok = kernels_mode(summary)
    else:
        full, tiny = _sizes()
        size = tiny if args.tiny else full
        devices = jax.devices()
        one = dict(zero_stage=1, mp_size=1, on_chip=on_chip)
        summary["train"] = [train_phase(size, devices[:1], parity=True, **one)]
        print(f"# train: {json.dumps(summary['train'][0])}", flush=True)
        if len(devices) > 1:
            base = summary["train"][0]["losses"][0]
            for layout in (dict(one),
                           dict(zero_stage=3, mp_size=2, on_chip=on_chip)):
                rec = train_phase(size, devices, parity=False, **layout)
                print(f"# train: {json.dumps(rec)}", flush=True)
                _check(abs(rec["losses"][0] - base) <= LAYOUT_LOSS0_ATOL,
                       f"{rec['layout']}: step-0 loss {rec['losses'][0]} vs "
                       f"{base} on one device (atol {LAYOUT_LOSS0_ATOL})")
                summary["train"].append(rec)
        summary["serve"] = serve_phase(size, devices[0])
        print(f"# serve: {json.dumps(summary['serve'])}", flush=True)
        ok = True
    summary["wall_s"] = round(time.perf_counter() - t_start, 1)
    summary["peak_memory"] = _mem(jax.devices())
    summary["ok"] = ok
    summary["claim"] = None

    out_dir = CHECKOUT / "chiprun_out"
    out_dir.mkdir(exist_ok=True)
    name = "chip_smoke_kernels.json" if args.kernels else "chip_smoke.json"
    (out_dir / name).write_text(json.dumps(summary, indent=1))
    print(json.dumps(summary), flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
