"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell: set-up (weights from the seed, compile or cache load,
warm-up, fill-up), the measured window, then the comparison that decides
``correct``. The last line of standard output is the result object. It
measures a chip: without an accelerator, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

import time

_PROCESS_START = time.perf_counter()

import argparse      # noqa: E402
import sys           # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def measure(cell, seed, seconds, trace, clock0=None, limits=None) -> tuple:
    """Drive one run of ``cell`` on whatever devices JAX has (the look for a
    chip is :func:`main`'s); returns the result line and the checks."""
    from benchmark import harness
    clock0 = time.perf_counter() if clock0 is None else clock0
    spans = harness.Spans()
    tracer = harness.Tracer(bool(trace), spans)
    compiles = harness.CompileCounter()
    kind = harness.load_named("kinds", cell.traffic["kind"])
    if trace:       # a traced run measures a short window: captures are
        # large and reading one takes longer than the window it covers
        seconds = min(seconds, float(cell.traffic.get("traced_seconds",
                                                      seconds)))
    spans.rows.append(("setup.import", clock0, time.perf_counter()))
    out = kind.run(cell, seed, seconds, tracer, spans, compiles)
    checks = harness.judge(out["numbers"],
                           cell.limits if limits is None else limits)
    correct = all(ok for *_, ok in checks) and out["failed"] == 0 \
        and out["attempted"] > 0
    device = dict(harness.device_info(out["devices"]),
                  memory_peak_bytes=out["memory_peak_bytes"])
    breakdown = None
    if trace:
        reduced = tracer.reduced
        device.update(busy_s=reduced.busy_s, window_s=reduced.window_s,
                      trace_cut_s=reduced.cut_s)
        breakdown = reduced.breakdown()
        ctx = {"cell": cell, "records": out["records"], "trace": reduced,
               "spans": spans.rows, "device_kind": device["kind"]}
        metrics = {}
        for m in cell.per_layer:
            value = harness.load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = (float(value), m["unit"])
    else:
        metrics = dict(out["end_to_end"])
        metrics["setup_s"] = (tracer.opened_at - clock0, "s")
        units = {m["name"]: m["unit"] for m in cell.end_to_end}
        assert set(metrics) == set(units), (sorted(metrics), sorted(units))
    out["phases"] = spans.phases()
    line = harness.result_line(
        correct=correct, attempted=out["attempted"], failed=out["failed"],
        metrics=metrics, device=device, breakdown=breakdown, checks=checks,
        phases=out["phases"])
    return line, checks, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmark import harness
    cell = harness.load_cell(args.workload)
    harness.place_compile_cache()
    harness.require_chips(cell.chips)
    line, checks, out = measure(cell, args.seed, args.seconds, args.trace,
                                clock0=_PROCESS_START)
    harness.report(line, checks, out["phases"])
    return 0


if __name__ == "__main__":
    sys.exit(main())
