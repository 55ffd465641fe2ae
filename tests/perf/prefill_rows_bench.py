"""Prefill program micro-benchmark: the program alone at R rows, on one chip.

At a served configuration's widths (``benchmark/configs/<name>.json``,
bfloat16, seeded weights as the benchmark makes them) it times the
serving prefill program (``PagedRunner._prefill``) carrying R chunks of
``--chunk`` tokens, each of its own slot at ``--start`` tokens of cached
past: R = 1 in the lone-slot form (no row dimension: what the engine
dispatches where a call carries one chunk) and in the row form, then the
rows of ``--rows``, each full and with one chunk and pad rows (the
program runs such a call's chunk alone). A call's time is the program's
median duration on the profiler trace's ``XLA Modules`` line
(``benchmark/trace.py``) over
``--reps`` calls; ``dispatch_ms`` is the median host time until a call
returns. It fits ``a + R * b`` to the lone call and the rows over 1 by
least squares: ``a`` is what a call pays whatever it carries (the
weights' read among it), ``b`` what each chunk adds.

Run on the TPU:  python tests/perf/prefill_rows_bench.py
[--configs gpt2-medium,gpt2-xl] [--rows 2,4,8] [--out rows.json]
(exits non-zero without an accelerator). Prints one JSON line per
variant and one per fit, and with ``--out`` writes them all to that file.
"""

import argparse
import json
import os
import statistics
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_SIZE = 16


def _program_ms(call, reps):
    """Median device duration of the prefill programs over ``reps`` traced
    calls, and the median host time until a call returned."""
    from benchmark import trace
    call()
    jax.block_until_ready(call())               # compile and warm
    returns = []
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(reps):
            t0 = time.perf_counter()
            out = call()
            returns.append(time.perf_counter() - t0)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        programs = trace.reduce(trace.find(d), chips=1).programs
    runs = [s for name, v in programs.items() if "prefill" in name
            for s in v]
    return 1e3 * statistics.median(runs), 1e3 * statistics.median(returns)


def _fit(points):
    """Least squares ``t = a + R * b`` over ``[(R, t)]``."""
    R = np.array([p[0] for p in points], float)
    t = np.array([p[1] for p in points], float)
    b, a = np.polyfit(R, t, 1)
    return float(a), float(b)


def bench(name, rows, chunk, start, reps, device):
    from benchmark import harness
    from deepspeed_tpu.serving.kv_cache import PagedKVCache
    from deepspeed_tpu.serving.runner import PagedRunner, cache_rows
    config = json.loads((harness.HERE / "configs" / f"{name}.json")
                        .read_text())
    ref = harness.load_named("reference", config["reference"])
    model = harness.load_named("programs", config["reference"]).model(config)
    params = ref.make_weights(ref.seed_words(7), ref.sizes(config),
                              jnp.bfloat16)
    MB = -(-config["n_positions"] // BLOCK_SIZE)
    R_most = max(rows)
    cache = PagedKVCache(config["n_layer"], block_size=BLOCK_SIZE,
                         num_blocks=1 + R_most * MB, dtype=jnp.bfloat16,
                         **cache_rows(model.config))
    runner = PagedRunner(model, cache)
    state = {"pools": cache.init_pools()}
    rng = np.random.default_rng(0)

    def args(R, form):
        bt = 1 + np.arange(R * MB, dtype=np.int32).reshape(R, MB)
        tok = rng.integers(0, config["vocab_size"], (R, chunk)).astype(
            np.int32)
        starts = np.full((R,), start, np.int32)
        n_valid = np.full((R,), chunk, np.int32)
        if form == "one":                       # pad rows after the first
            bt[1:], n_valid[1:], starts[1:] = 0, 0, 0
        a = (bt, tok, starts, n_valid, np.arange(R, dtype=np.int32))
        return tuple(x[0] for x in a) if form == "lone" else a

    def call_with(a):
        def call():
            state["pools"], _ = runner._prefill(params, {}, state["pools"],
                                                *a)
            return state["pools"]
        return call

    out, points = [], []
    variants = [(1, "lone"), (1, "rows")] + [
        (R, form) for R in rows for form in ("rows", "one")]
    for R, form in variants:
        program_ms, dispatch_ms = _program_ms(call_with(args(R, form)), reps)
        chunks = 1 if form == "one" else R
        row = {"config": name, "rows": R, "form": form, "chunk": chunk,
               "start": start, "program_ms": program_ms,
               "ms_a_chunk": program_ms / chunks, "dispatch_ms": dispatch_ms,
               "device": device}
        out.append(row)
        print(json.dumps(row), flush=True)
        if form == "lone" or (form == "rows" and R > 1):
            points.append((R, program_ms))
    a, b = _fit(points)
    fit = {"config": name, "fit": "a + R * b", "a_ms": a, "b_ms": b,
           "points": points, "device": device}
    out.append(fit)
    print(json.dumps(fit), flush=True)
    del params, state["pools"], runner
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="gpt2-medium,gpt2-xl")
    ap.add_argument("--rows", default="2,4,8")
    ap.add_argument("--chunk", type=int, default=128)
    ap.add_argument("--start", type=int, default=128,
                    help="cached tokens ahead of each row's chunk")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--out", help="also write every row to this JSON file")
    a = ap.parse_args()

    from deepspeed_tpu.utils.chip import (enable_compile_cache,
                                          require_accelerator)
    enable_compile_cache()
    device = require_accelerator()
    rows = [int(r) for r in a.rows.split(",")]
    out = []
    for name in a.configs.split(","):
        out += bench(name, rows, a.chunk, a.start, a.reps, device)
    if a.out:
        with open(a.out, "w") as f:
            json.dump(out, f, indent=1)


if __name__ == "__main__":
    main()
