"""How far bfloat16 alone moves the greedy tokens of a served
``nemotron_h`` configuration, and how much of that is the expert layers'
routing, on the plain reference (``benchmark/reference/nemotron_h.py``):
rows of seeded token ids through three forwards that run side by side,
layer by layer,

* ``f32``: the reference as the benchmark computes it;
* ``bf16``: the operands of every product the configuration states in
  bfloat16 rounded to bfloat16 (the reference's ``quant="bfloat16"``),
  the router and the state in float32, as the program has them;
* ``pinned``: ``bf16`` with every expert layer routed as ``f32`` routes
  the same position.

For ``bf16`` and ``pinned`` it prints, over every position, the mean and
the widest gap by which the token that forward puts first lies below the
float32 forward's best logit (what ``top_gap_mean`` and ``top_gap_max``
read of a served token), the largest of the rows' mean gaps, and the share
of (position, expert layer) whose chosen experts differ between ``f32``
and ``bf16``.

Run on the TPU:  python tests/perf/route_flips.py
[--config NVIDIA-Nemotron-3-Nano-30B-A3B-BF16] [--rows 12] [--length 2048]
"""

import argparse
import json
import sys
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

BF16 = "bfloat16"       # the reference's ``quant`` that rounds to it


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--config", default="NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    ap.add_argument("--config-file", default=None)
    ap.add_argument("--rows", type=int, default=12)
    ap.add_argument("--length", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=4200600001)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from benchmark.reference import nemotron_h as ref

    root = Path(__file__).resolve().parents[2]
    config = json.loads(Path(args.config_file or root / "benchmark" / "configs"
                             / f"{args.config}.json").read_text())
    sz = ref.sizes(config)
    dtype = jnp.dtype(config["precision"])
    words = ref.seed_words(args.seed)
    ids = np.random.default_rng(args.seed).integers(
        0, sz.V, (args.rows, args.length), dtype=np.int32)
    T = -(-args.length // ref.BLOCK) * ref.BLOCK
    ids = ref._padded(ids, args.length)
    starts = range(0, T, ref.BLOCK)
    outer = ref._cast(ref._outer_in(words, sz, dtype), jnp.float32)
    xs = {k: [outer["embed"][jnp.asarray(r)] for r in ids]
          for k in ("f32", "bf16", "pinned")}
    flips = seen = 0
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(sz.kinds):
            p = ref.layer_as_served(words, i, sz, dtype)
            if kind == "E":
                for a, b in zip(xs["f32"], xs["bf16"]):
                    ca, cb = (jnp.sort(ref._route(ref._rms(
                        x[:args.length], p["norm_1"], sz.eps), p["moe"],
                        sz)[0], -1) for x in (a, b))
                    flips += int((ca != cb).any(-1).sum())
                    seen += args.length
                pinned = []
                for x, r in zip(xs["pinned"], xs["f32"]):
                    cap = ref._capacity(max(int(ref._expert_load(
                        r[s:s + ref.BLOCK], p, sz=sz)) for s in starts))
                    pinned.append(jnp.concatenate([ref._expert_block(
                        x[s:s + ref.BLOCK], p, sz=sz, cap=cap, quant=BF16,
                        route=r[s:s + ref.BLOCK]) for s in starts]))
            else:
                pinned = [ref._layer_row(x, p, kind, sz, BF16)
                          for x in xs["pinned"]]
            xs = {"f32": [ref._layer_row(x, p, kind, sz, False)
                          for x in xs["f32"]],
                  "bf16": [ref._layer_row(x, p, kind, sz, BF16)
                           for x in xs["bf16"]],
                  "pinned": pinned}
            del p

    def read(x, picks, quant):
        return np.concatenate([np.asarray(ref._read_rows(
            x[s:s + ref.BLOCK], outer["norm_f"], outer["head"],
            jnp.asarray(picks[s:s + ref.BLOCK]), sz=sz, quant=quant))
            for s in starts])[:args.length]

    out = {"rows": args.rows, "length": args.length, "seed": args.seed,
           "route_flip_share": flips / seen}
    for name in ("bf16", "pinned"):
        gaps = []
        for x, x32, row in zip(xs[name], xs["f32"], ids):
            first = read(x, row, BF16)[:, 2].astype(np.int32)
            top, picked, _ = read(
                x32, ref._padded(first[None], args.length)[0], False).T
            gaps.append(top - picked)
        gaps = np.stack(gaps)
        out[name] = {"gap_mean": float(gaps.mean()),
                     "gap_max": float(gaps.max()),
                     "row_mean_max": float(gaps.mean(1).max())}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
