"""Flash-attention backward micro-benchmark: the one-pass backward against
the two-call pair it replaced, kernel by kernel, on one chip.

At one attention shape (default gpt2-medium's training call: batch 8, 16
heads, 1,024 positions, head 64, bf16, causal) it times ``flash_fwd``; the
pair ``flash_dq`` + ``flash_dkv``, each recomputing the scores; and the
one-pass kernel (also named ``flash_dkv``) at every (block_q, block_k) of
``--blocks``. A kernel's time is its summed device duration on the
profiler trace's ``XLA Ops`` line (``benchmark/trace.py``, the reduction
the benchmark's roofline readers use) over ``--reps`` calls, and each
variant is traced in a session of its own. It also checks that the
one-pass gradients equal the pair's.

Run on the TPU:  python tests/perf/flash_bwd_bench.py [--shape 8,16,1024,64]
[--out rows.json]  (exits non-zero without an accelerator). Prints one JSON
line per variant, and with ``--out`` writes them all to that file.
"""

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np


def _device_ms(fn, args, reps):
    """Per call: the summed device time of each operation named ``flash_*``
    over ``reps`` traced calls, and the wall time of the whole."""
    from benchmark import trace
    jax.block_until_ready(fn(*args))            # compile and warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        wall = time.perf_counter() - t0
        jax.profiler.stop_trace()
        ops = trace.reduce(trace.find(d), chips=1).ops
    kernels = {}
    for name, s in ops.items():
        if name.startswith("flash_"):
            base = name.split(".")[0]
            kernels[base] = kernels.get(base, 0.0) + 1e3 * s / reps
    return kernels, 1e3 * wall / reps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shape", default="8,16,1024,64")
    ap.add_argument("--blocks", default="512x512,512x256,256x256,256x512,"
                    "128x512,512x128,128x256,256x128,128x128",
                    help="block_q x block_k pairs of the one-pass kernel")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", help="also write every row to this JSON file")
    a = ap.parse_args()

    from deepspeed_tpu.utils.chip import (enable_compile_cache,
                                          require_accelerator)
    enable_compile_cache()
    device = require_accelerator()
    from deepspeed_tpu.ops.transformer import flash

    B, H, S, D = (int(x) for x in a.shape.split(","))
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(kk, (B, H, S, D), jnp.bfloat16)
                   for kk in keys)
    sm = D ** -0.5
    out, res = jax.jit(lambda q, k, v: flash._flash_fwd(q, k, v, True, sm))(
        q, k, v)
    lse = res[4]
    qf, kf, vf, dof = (x.reshape(B * H, S, D) for x in (q, k, v, do))
    delta = jnp.broadcast_to(jnp.sum(
        dof.astype(jnp.float32) * out.reshape(B * H, S, D).astype(
            jnp.float32), axis=-1, keepdims=True), (B * H, S, flash.LANES))
    bwd_args = (qf, kf, vf, dof, lse, delta)
    bq0 = flash._pick_block(S)

    def bwd(form, bq, bk):
        return jax.jit(lambda *x: form(*x, sm_scale=sm, causal=True,
                                       block_q=bq, block_k=bk))

    rows = []

    def record(variant, fn, args, **extra):
        kernels, wall = _device_ms(fn, args, a.reps)
        row = {"variant": variant, "shape": [B, H, S, D],
               "kernel_ms": {n: round(t, 4) for n, t in kernels.items()},
               "device_ms": round(sum(kernels.values()), 4),
               "wall_ms": round(wall, 4), **extra, "device": device}
        rows.append(row)
        print(json.dumps(row), flush=True)

    record("fwd", jax.jit(lambda q, k, v: flash._flash_fwd(
        q, k, v, True, sm)[0]), (q, k, v), block=[bq0, bq0])
    pair = bwd(flash._bwd_resident_pair, bq0, bq0)
    record("pair", pair, bwd_args, block=[bq0, bq0])
    ref = [np.asarray(x, np.float32) for x in pair(*bwd_args)]
    for pq, pk in (p.split("x") for p in a.blocks.split(",")):
        bq, bk = int(pq), int(pk)
        fused = bwd(flash._bwd_resident, bq, bk)
        got = [np.asarray(x, np.float32) for x in fused(*bwd_args)]
        gap = [float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
               for g, r in zip(got, ref)]
        record("one_pass", fused, bwd_args, block=[bq, bk],
               max_gap_vs_pair=dict(zip(("dq", "dk", "dv"), gap)))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
