"""What every cell shares: the manifest and the files it names, host spans,
the profiler window, the device's description, the result line.

Everything that belongs to one configuration, one traffic mix, one kind of
traffic or one per-layer metric sits in a file of its own, found by the
name ``BENCHMARK.json`` gives: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``limits/<cell>.json``,
``kinds/<kind>.py``, ``reference/<reference>.py``,
``programs/<program>.py`` and ``metrics/<metric>.py``.
"""

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
TRACE_DIR = ROOT / ".bench_trace"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


# ------------------------------------------------------------------ manifest
@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple       # metric entries this cell reports
    per_layer: tuple
    limits: dict            # number -> {"limit", "lower", "upper"}


def load_manifest(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _applies(metric: dict, cell_name: str, reported=None) -> bool:
    """A metric with a ``workloads`` key is for those cells; without one an
    end-to-end metric is for every cell, and a per-layer metric for every
    cell that reports the end-to-end metric it moves."""
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return reported is None or metric["moves"] in reported


def load_cell(name: str, manifest: dict = None, root: Path = ROOT) -> Cell:
    manifest = manifest or load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                         f"({sorted(cells)})")
    w = cells[name]
    entry = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    traffic = json.loads(
        (HERE / "traffic" / f"{w['traffic']}.json").read_text())
    if int(traffic["chips"]) != int(w["chips"]):
        raise SystemExit(f"{name}: the manifest asks for {w['chips']} chips, "
                         f"the traffic file for {traffic['chips']}")
    e2e = tuple(m for m in manifest["end_to_end"] if _applies(m, name))
    reported = {m["name"] for m in e2e}
    per_layer = tuple(m for m in manifest["per_layer"]
                      if _applies(m, name, reported))
    limits_file = HERE / "limits" / f"{name}.json"
    limits = json.loads(limits_file.read_text())["numbers"]
    return Cell(name, config, traffic, int(w["chips"]), e2e, per_layer,
                limits)


def load_named(package: str, name: str):
    """``benchmark/<package>/<name>.py`` as a module (``-`` reads ``_``)."""
    return importlib.import_module(
        f"benchmark.{package}.{name.replace('-', '_')}")


def load_reader(metric: str):
    path = HERE / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "benchmark_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# -------------------------------------------------------------------- device
def place_compile_cache() -> None:
    """JAX's persistent compile cache where the program keeps it (the
    directory ``JAX_COMPILATION_CACHE_DIR`` names, else the fixed
    ``<checkout>/.jax_compilation_cache``), holding every program: where
    the directory is placed from outside the program sets no thresholds,
    and a warm run would compile each small program again."""
    import jax
    from deepspeed_tpu.utils.chip import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def require_chips(n: int) -> None:
    """Measuring needs the accelerator: no fallback to the CPU, and no
    result with fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    if devices[0].platform == "cpu" or len(devices) < n:
        raise SystemExit(
            f"no accelerator for this cell: jax.devices() found "
            f"{len(devices)} x {devices[0].device_kind} "
            f"({devices[0].platform}), the cell needs {n} chip(s); the "
            f"benchmark measures a chip and does not fall back")


def device_info(devices) -> dict:
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip (0 where the backend keeps no statistics)."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks))


class CompileCounter:
    """Counts JAX's backend-compile events (a cache load counts: it is work
    the window must not hold)."""

    def __init__(self):
        import jax.monitoring
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == _BACKEND_COMPILE:
            self.count += 1


# --------------------------------------------------------------------- spans
class Spans:
    """Host spans, kept in memory: (name, start, end) on the host's
    ``perf_counter``. While the profiler runs they are also written into
    its trace as ``bench:<name>`` so that device idle gaps can be named by
    what the host was doing."""

    PREFIX = "bench:"

    def __init__(self):
        self.rows = []
        self.annotate = False

    def phases(self) -> dict:
        """Seconds under each ``setup.*`` / ``trace.*`` / ``check.*`` span:
        where a run's time outside the window goes (stderr and the line's
        ``phases``)."""
        out = {}
        for name, t0, t1 in self.rows:
            if name.startswith(("setup.", "trace.", "check.")):
                out[name] = out.get(name, 0.0) + (t1 - t0)
        return out

    @contextlib.contextmanager
    def __call__(self, name):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(self.PREFIX + name):
                yield
        else:
            yield
        self.rows.append((name, t0, time.perf_counter()))


class Tracer:
    """The profiler around the measured window, in the ``--trace 1`` run
    only. End-to-end numbers are taken with it off."""

    def __init__(self, on: bool, spans: Spans):
        self.on = on
        self.spans = spans
        self.reduced = None
        self.opened_at = None       # set-up ends where the window opens

    @contextlib.contextmanager
    def window(self, devices, settle=None):
        """``settle`` runs uncounted steps between starting the profiler and
        opening the window: the first programs run under it may stall while
        the device's tracing starts up (13 s for gpt2-xl's training step, PR
        25). How many is the kind's to say, and few: the capture holds them
        too, and the device's trace buffer is bounded (``trace.py``)."""
        self.opened_at = time.perf_counter()
        if not self.on:
            yield
            return
        import jax
        from benchmark import trace
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
        self.spans.annotate = True
        try:
            if settle is not None:
                settle()
            with self.spans("window"):
                yield
        finally:
            self.spans.annotate = False
            with self.spans("trace.stop"):
                jax.profiler.stop_trace()
        with self.spans("trace.reduce"):
            self.reduced = trace.reduce(trace.find(TRACE_DIR), len(devices),
                                        Spans.PREFIX)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


# -------------------------------------------------------------------- result
def judge(numbers: dict, limits: dict) -> list:
    """[(name, value, limit, ok)] for every number compared; a number with
    no entry in the cell's file is an error, not a pass. An entry whose
    limit is null names a number that is read but not compared (its
    readings separate nothing: the file says why)."""
    rows = []
    for name, value in numbers.items():
        if limits[name]["limit"] is None:
            continue
        limit = float(limits[name]["limit"])
        ok = bool(value == value and value <= limit)       # NaN fails
        rows.append((name, float(value), limit, ok))
    return rows


def result_line(*, correct, attempted, failed, metrics, device, breakdown,
                checks, phases=None) -> str:
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": v, "unit": u}
                        for k, (v, u) in metrics.items()},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if phases:
        line["phases"] = phases
    line["checks"] = {n: {"value": v, "limit": lim, "ok": ok}
                      for n, v, lim, ok in checks}
    return json.dumps(line)


def report(line: str, checks, phases) -> None:
    sys.stdout.flush()
    for name, seconds in phases.items():
        print(f"phase {name}: {seconds:.2f} s", file=sys.stderr)
    for n, v, lim, ok in checks:
        print(f"check {n}: {v:.6g} (limit {lim:.6g}) "
              f"{'ok' if ok else 'NOT CORRECT'}", file=sys.stderr)
    sys.stderr.flush()
    print(line, flush=True)
