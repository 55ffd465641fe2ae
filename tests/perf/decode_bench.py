"""Inference decode benchmark — KV-cache generation throughput.

The training bench (bench.py) covers the reference's training-kernel
claims; this measures the inference side (the csrc/transformer/inference
kernel surface): per-token latency of cached greedy decoding on one chip.

Run on the TPU:  python tests/perf/decode_bench.py  (exits non-zero
without an accelerator — it measures a chip and does not fall back)
Env: DECODE_MODEL (gpt2|gpt2-medium), DECODE_BS, DECODE_PROMPT,
DECODE_NEW (defaults 8 / 32 / 128 new tokens).
Prints one JSON line: tokens/s and ms/token, and the device it ran on.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import numpy as np


def main():
    from deepspeed_tpu.utils.chip import (enable_compile_cache,
                                          require_accelerator)
    enable_compile_cache()
    device = require_accelerator()

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import PRESETS

    name = os.environ.get("DECODE_MODEL", "gpt2-medium")
    bs = int(os.environ.get("DECODE_BS", "8"))
    prompt_len = int(os.environ.get("DECODE_PROMPT", "32"))
    new_tokens = int(os.environ.get("DECODE_NEW", "128"))
    cfg = PRESETS[name]
    kv = os.environ.get("DECODE_KV", "auto")   # auto | int8 (KV cache)
    if kv != "auto":
        import dataclasses
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv)

    from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel
    model = GPT2LMHeadModel(cfg)
    import jax.numpy as jnp
    ids = jnp.zeros((bs, prompt_len), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), {"input_ids": ids})["params"]
    # DECODE_DTYPE=int8: module_quantize path (int8 weight storage,
    # dequant folded into the matmuls)
    dt_name = os.environ.get("DECODE_DTYPE", "bf16")
    dtype = {"bf16": None, "int8": jnp.int8}[dt_name]
    eng = deepspeed_tpu.init_inference(model, params=params, dtype=dtype)

    prompt = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (bs, prompt_len)), jnp.int32)

    def timed(n_new):
        jax.block_until_ready(
            eng.generate(prompt, max_new_tokens=n_new))     # compile
        t0 = time.perf_counter()
        reps = 3
        for _ in range(reps):
            out = eng.generate(prompt, max_new_tokens=n_new)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    dt = timed(new_tokens)
    # isolate steady-state decode: subtract a short-generation run so the
    # amortised prefill cost drops out of the per-step figure (needs two
    # distinct lengths; clamped non-negative against timing noise)
    short = max(1, new_tokens // 8)
    if short < new_tokens:
        dt_short = timed(short)
        per_step_ms = max(0.0, (dt - dt_short) / (new_tokens - short) * 1e3)
    else:
        per_step_ms = dt / new_tokens * 1e3

    total_new = bs * new_tokens
    print(json.dumps({
        "metric": f"{name} cached decode (bs={bs} prompt={prompt_len} "
                  f"new={new_tokens}, {dt_name}, kv={kv})",
        "device": device,
        "tokens_per_s": round(total_new / dt, 1),
        "ms_per_token_step": round(per_step_ms, 3),
        "batch_latency_s": round(dt, 3),
    }))


if __name__ == "__main__":
    main()
