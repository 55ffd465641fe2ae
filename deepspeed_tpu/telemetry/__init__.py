"""Structured telemetry subsystem.

Four pieces (see the per-module docstrings):

* ``tracer`` — nested ``trace_span`` contexts -> Chrome-trace JSON
  (live when enabled or while a JAX profiler session runs, when each span
  is also a ``jax.profiler.TraceAnnotation`` in the capture), and the
  process's one garbage-collection hook (``watch_gc``: each collection a
  ``<loop>_gc`` span and counters of the engine loop that owns it);
* ``compile_watch`` — XLA compile counting + retrace culprit reports;
* ``metrics`` — counters / gauges / histograms + device-memory stats;
* ``sinks`` — JSONL event writer and Prometheus text-format exporter
  (both also usable as ``MonitorMaster`` backends);
* ``hlo_census`` — structured census of a compiled XLA program: cost /
  memory analysis + a real HLO parser for per-collective byte volumes
  and mesh-axis attribution;
* ``cost_explorer`` — joins the census with runtime timings: roofline /
  MFU attribution, bound-ness verdicts, HBM watermark pre-flight
  (``python -m deepspeed_tpu.telemetry.explain`` is the CLI);
* ``health`` — training-health observatory: in-step numerics stats
  (grad/param/update norms, per-module buckets, loss-scale state,
  non-finite provenance), EWMA/z-score anomaly rules, HEALTH.json
  forensics (``python -m deepspeed_tpu.telemetry.health`` is the CLI);
* ``ledger`` — goodput ledger: wall-clock attribution into named
  categories that sum to elapsed time, input-stall / unattributed-
  residual rules, GOODPUT.json forensics and on-anomaly programmatic
  profiler capture (``python -m deepspeed_tpu.telemetry.ledger``);
* ``serving_observatory`` — the serving-side counterpart: per-request
  lifecycle timelines (per-slot Chrome-trace lanes), the slot-step
  attribution ledger (categories sum to steps x max_batch x
  decode_steps by construction), windowed SLO rules and
  SERVING_HEALTH.json forensics
  (``python -m deepspeed_tpu.telemetry.serving_observatory``);
* ``fleet`` — the cross-rank flight recorder: every rank ships atomic
  window records into a shared run dir, rank 0 merges them and runs the
  straggler/input/checkpoint skew sentinels plus the desync sentinel
  (cross-replica parameter checksums), escalating to
  FLEET_HEALTH.json; ``merge_traces`` joins per-rank Chrome traces into
  per-rank process lanes (``python -m deepspeed_tpu.telemetry.fleet``);
* ``xplane`` / ``step_anatomy`` — measured device-time attribution:
  a dependency-free wire-format parser for the XSpace protobuf
  ``jax.profiler`` writes, and the StepAnatomy join (per-op device
  seconds -> categories/modules vs the CostExplorer roofline) behind
  ``engine.profile_step`` / ``ServingEngine.profile_window`` ->
  STEP_ANATOMY.json (``python -m deepspeed_tpu.telemetry.step_anatomy``
  is the CLI). Deliberately NOT imported here: the parser only loads
  when a capture is post-processed (lazy ``__getattr__`` below), so
  engine init never pays for it — tests/perf/telemetry_overhead.py
  pins that;
* ``pprof`` / ``memory_observatory`` — measured device-MEMORY
  attribution: a dependency-free parser for the gzip+protobuf pprof
  profile ``jax.profiler.device_memory_profile()`` emits, and the HBM
  residency observatory (exact-sum buffer attribution into
  params / optimizer_state / kv_pool / activations_workspace / other,
  leak / watermark-drift / kv-fragmentation / oom-risk sentinels) behind
  ``telemetry.memory`` + ``engine.memory_report`` -> MEMORY_ANATOMY.json
  (``python -m deepspeed_tpu.telemetry.memory_observatory`` is the
  CLI). Lazy like xplane/step_anatomy — only loads at the first cadence
  tick;
* ``clock`` — the shared monotonic integer-µs axis every cross-stream
  timestamp joins on (plus the one wall anchor for rendering);
* ``escalation`` — the ONE escalation protocol all five observatories
  share (warn-once, counters, history cap, snapshot, chronicle emit,
  fenced hooks);
* ``chronicle`` / ``incidents`` — the run chronicle (one causally-
  ordered event timeline across monitors, guardian, engine lifecycle,
  serving and chaos; per-rank atomic JSONL streams) and the incident
  correlator joining it into INCIDENTS.json chains with ranked root
  cause and per-incident goodput cost
  (``python -m deepspeed_tpu.telemetry.chronicle`` is the CLI);
* ``obs_server`` — the live observability plane: a zero-dependency
  HTTP endpoint (``telemetry.server`` config block) serving /metrics
  (a real Prometheus scrape target), /healthz + /readyz probes, every
  armed monitor's host-side report under /api/report/<name>, and the
  resumable chronicle tail under /api/events — a scrape never forces a
  device fetch, sync, or compile. Lazy like xplane (below);
* ``slo`` — the SLO burn-rate monitor (``telemetry.slo`` block):
  multi-window error-budget burn over declarative latency/goodput
  objectives; fast+slow both burning pages ``slo_burn_page`` (a
  guardian admission-pause rule) -> SLO_REPORT.json
  (``python -m deepspeed_tpu.telemetry.slo --demo`` is the CLI). Lazy;
* ``dashboard`` — the mission-control terminal dashboard over either a
  live ``obs_server`` URL or an artifact dir
  (``python -m deepspeed_tpu.telemetry.dashboard --url/--dir``). Lazy;
* ``federation`` — fleet federation (``telemetry.federation`` block):
  every rank's obs server announces itself into a run-dir peer
  registry; the aggregator rank scrapes each peer's /metrics, reports
  and resumable /api/events over keep-alive HTTP and serves the
  rank-labelled merged scrape, one (t_us, seq, rank)-ordered fleet
  timeline, fleet-scope SLO burn with per-rank attribution and
  cross-rank incident chains under /federation/* and /api/fleet/* ->
  FLEET_CONTROL.json
  (``python -m deepspeed_tpu.telemetry.federation --demo``). Lazy.

``TelemetryManager`` (manager.py) wires them per engine run, behind the
``telemetry`` config block (see CONFIG.md). Everything is importable and
near-free when disabled: ``trace_span`` on the default (disabled) global
tracer is a shared no-op context manager.
"""

from deepspeed_tpu.telemetry.tracer import (Tracer, get_tracer, set_tracer,
                                            trace_span)
from deepspeed_tpu.telemetry.metrics import (Counter, Gauge, Histogram,
                                             MetricsRegistry,
                                             device_memory_stats,
                                             get_registry, set_registry)
from deepspeed_tpu.telemetry.compile_watch import CompileWatch
from deepspeed_tpu.telemetry.sinks import (JSONLMonitor, JSONLSink,
                                           PrometheusMonitor,
                                           PrometheusSink,
                                           render_prometheus)
from deepspeed_tpu.telemetry.hlo_census import (CollectiveOp, HloCensus,
                                                census_compiled, census_fn,
                                                parse_hlo_collectives,
                                                parse_replica_groups)
from deepspeed_tpu.telemetry.cost_explorer import CostExplorer, detect_chip
from deepspeed_tpu.telemetry.health import (BucketSpec, HealthMonitor,
                                            bucket_grad_stats,
                                            build_bucket_spec,
                                            decode_nonfinite_mask)
from deepspeed_tpu.telemetry.ledger import (GoodputIterator, GoodputLedger,
                                            get_ledger, set_ledger)
from deepspeed_tpu.telemetry.serving_observatory import (RequestTimeline,
                                                         ServingObservatory,
                                                         SlotStepLedger)
from deepspeed_tpu.telemetry.fleet import (FleetMonitor, FleetShipper,
                                           build_desync_checksum_fn,
                                           get_shipper, merge_traces,
                                           set_shipper)
from deepspeed_tpu.telemetry.chronicle import (RunChronicle, get_chronicle,
                                               reset_chronicle,
                                               set_chronicle)
from deepspeed_tpu.telemetry.incidents import (IncidentCorrelator,
                                               correlate, write_incidents)
from deepspeed_tpu.telemetry.manager import (TelemetryManager, get_manager,
                                             set_manager)

__all__ = [
    "Tracer", "get_tracer", "set_tracer", "trace_span",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "device_memory_stats", "get_registry", "set_registry",
    "CompileWatch", "JSONLMonitor", "JSONLSink", "PrometheusMonitor",
    "PrometheusSink", "render_prometheus", "TelemetryManager",
    "CollectiveOp", "HloCensus", "census_compiled", "census_fn",
    "parse_hlo_collectives", "parse_replica_groups",
    "CostExplorer", "detect_chip",
    "BucketSpec", "HealthMonitor", "bucket_grad_stats",
    "build_bucket_spec", "decode_nonfinite_mask",
    "GoodputIterator", "GoodputLedger", "get_ledger", "set_ledger",
    "RequestTimeline", "ServingObservatory", "SlotStepLedger",
    "FleetMonitor", "FleetShipper", "build_desync_checksum_fn",
    "get_shipper", "merge_traces", "set_shipper",
    "get_manager", "set_manager",
    "RunChronicle", "get_chronicle", "set_chronicle", "reset_chronicle",
    "IncidentCorrelator", "correlate", "write_incidents",
    "xplane", "step_anatomy", "pprof", "memory_observatory",
    "obs_server", "slo", "dashboard", "federation",
]


def __getattr__(name):
    # lazy submodule access (PEP 562): telemetry.xplane / .step_anatomy /
    # .pprof / .memory_observatory stay un-imported until a capture or a
    # residency window is actually post-processed; obs_server / slo /
    # dashboard / federation until the mission-control plane is armed
    if name in ("xplane", "step_anatomy", "pprof", "memory_observatory",
                "obs_server", "slo", "dashboard", "federation"):
        import importlib
        return importlib.import_module(f"deepspeed_tpu.telemetry.{name}")
    raise AttributeError(
        f"module {__name__!r} has no attribute {name!r}")
