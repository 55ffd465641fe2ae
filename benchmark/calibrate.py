"""Readings that a cell's limits are set from, taken on the chip at the
cell's own size, in one process:

    python3 benchmark/calibrate.py --workload <cell> --seeds 12 \
        --control-seeds 3 --seconds 5 --out chiprun_out/<file>.json

For every seed: a short run of the cell as the benchmark drives it (the
LOWER readings: the program's numbers against the plain reference). For the
first ``--control-seeds`` of them also the UPPER readings: the CONTROL (the
reference computed in the nearest precision below the configuration's, put
in the program's place) and, for training, the planted fault "half of the
batch left out, the mean taken over the rest". A step that returns its state
unchanged reads 1 by the measure used and needs no run. The limits in
``limits/<cell>.json`` are written by hand from this file's output, with
the readings beside them.
"""

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


class _NoLimits(dict):          # readings first: the limits come from them
    def __missing__(self, name):
        return {"limit": float("inf")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    from benchmark import harness
    from benchmark.run import measure
    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    harness.require_chips(cell.chips)
    kind = harness.load_named("kinds", cell.traffic["kind"])
    rows = []
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        t0 = time.perf_counter()
        line, _, out = measure(cell, seed, args.seconds, 0, limits=_NoLimits())
        row = {"seed": seed, "sound": out["numbers"],
               "line": json.loads(line)}
        if k < args.control_seeds:
            if cell.traffic["kind"] == "train":
                want = out["reference"]
                half = slice(0, cell.traffic["global_batch"] // 2)
                row["control"] = kind.numbers(
                    kind.reference_readings(cell, seed, quant=True), want)
                row["fault_half_batch"] = kind.numbers(
                    kind.reference_readings(cell, seed, rows=half), want)
            else:
                row["control"] = kind.numbers(cell, seed, out["evidence"],
                                              quant=True)
                row["tokens_compared"] = sum(len(t) for _, t in out["evidence"])
        row["seconds"] = time.perf_counter() - t0
        rows.append(row)
        print(json.dumps(row), flush=True)
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
