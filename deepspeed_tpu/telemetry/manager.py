"""TelemetryManager — one object owning the per-run telemetry state.

Constructed by the engine from the parsed ``telemetry`` config block.
Rank-0 only (like ``MonitorMaster``): non-zero ranks get the disabled
manager whose every surface is a no-op, so engine call sites need no rank
checks. When enabled it:

* installs its ``Tracer`` / ``MetricsRegistry`` as the process globals so
  library code (``checkpoint_io``, timers) reaches them via
  ``telemetry.trace_span`` / ``metrics.get_registry`` without plumbing;
* arms the compile watch (wrapping happens at the engine, which knows its
  jitted entry points) and the jax.monitoring backend-compile listener;
* exports the Chrome trace on ``flush()`` (the engine calls it at
  ``steps_per_print`` cadence) and once more at interpreter exit, so a
  crashed or un-torn-down run still leaves a readable trace.

File layout under ``<output_path>/``: ``<job>.trace.json`` (Chrome trace),
``<job>.jsonl`` + ``<job>.prom`` (written by the MonitorMaster sinks which
share this manager's registry).
"""

import atexit
import os

from deepspeed_tpu.telemetry import compile_watch as _cw
from deepspeed_tpu.telemetry import metrics as _metrics
from deepspeed_tpu.telemetry import tracer as _tracer_mod
from deepspeed_tpu.telemetry.metrics import device_memory_stats


class TelemetryManager:
    def __init__(self, config=None, rank=0):
        self.config = config
        self.enabled = bool(config is not None
                            and getattr(config, "enabled", False)
                            and rank == 0)
        if not self.enabled:
            self.tracer = None      # spans go to the process-global tracer
            self.registry = None
            self.compile_watch = None
            self.trace_path = None
            self.health = None
            self.goodput = None
            self.memory = None
            return

        out = config.output_path or "telemetry/"
        job = config.job_name or "DeepSpeedJobName"
        os.makedirs(out, exist_ok=True)
        self.output_path = out
        self.job_name = job
        self.trace_path = os.path.join(out, f"{job}.trace.json")

        self.registry = _metrics.MetricsRegistry()
        _metrics.set_registry(self.registry)
        self.tracer = _tracer_mod.Tracer(
            enabled=bool(config.trace),
            max_events=int(config.max_trace_events))
        _tracer_mod.set_tracer(self.tracer)
        self.compile_watch = (_cw.CompileWatch(self.registry)
                              if config.compile_watch else None)
        if config.compile_watch:
            _cw.install_global_listener(self.registry)
        # training-health observatory (telemetry/health.py): the monitor is
        # rank-0/host-side like everything here; the engine fills in the
        # mesh-dependent attributes (bucket names, fp16 min_scale, census
        # header) once its step functions exist, and feeds note_step /
        # observe from its train loop.
        self.health = None
        if getattr(config, "health_enabled", False):
            from deepspeed_tpu.telemetry.health import HealthMonitor
            on_escalate = (self._force_trace_export
                           if getattr(config, "health_trace_on_anomaly",
                                      True) and config.trace else None)
            self.health = HealthMonitor.from_config(
                config, output_path=out, job_name=job,
                registry=self.registry, on_escalate=on_escalate)
        # goodput ledger (telemetry/ledger.py): wall-clock attribution.
        # Installed as the process-global ledger so library code
        # (dataloader next(), checkpoint_io, the compile watch's
        # backend-compile listener) attributes without plumbing; the
        # engine wires the step-loop call sites and drives the ticks.
        self.goodput = None
        if getattr(config, "goodput_enabled", False):
            from deepspeed_tpu.telemetry import ledger as _ledger_mod
            self.goodput = _ledger_mod.GoodputLedger.from_config(
                config, output_path=out, job_name=job,
                registry=self.registry,
                on_escalate=(self._force_trace_export
                             if config.trace else None))
            _ledger_mod.set_ledger(self.goodput)
        # HBM residency observatory (telemetry/memory_observatory.py):
        # host-side like the health monitor; the engine fills in the
        # watermark prediction / HBM budget once its census exists and
        # feeds observe() from the cadence tick.
        self.memory = None
        if getattr(config, "memory_enabled", False):
            from deepspeed_tpu.telemetry.memory_observatory import \
                MemoryMonitor
            self.memory = MemoryMonitor.from_config(
                config, output_path=out, job_name=job,
                registry=self.registry,
                on_escalate=(self._force_trace_export
                             if config.trace else None))
        self._closed = False
        self._last_export_t = float("-inf")
        self._last_export_n = -1
        # process-global handle, mirroring tracer/metrics/ledger: code
        # that has no engine reference (the serving observatory's
        # trace-flush escalation) reaches the live manager through it
        set_manager(self)
        atexit.register(self.close)

    # ---------------------------------------------------------------- spans
    # a disabled manager owns no tracer: its spans are the process-global
    # tracer's, live while a profiler session runs, so there is ONE tracer
    def span(self, name, **args):
        return (self.tracer or _tracer_mod.get_tracer()).span(name, **args)

    def instant(self, name, **args):
        (self.tracer or _tracer_mod.get_tracer()).instant(name, **args)

    # -------------------------------------------------------------- compile
    def wrap_compiled(self, fn, name):
        """Compile-watch instrumentation for a jitted entry point; identity
        when disabled (or fn is None)."""
        if fn is None or self.compile_watch is None:
            return fn
        return self.compile_watch.wrap(fn, name)

    # -------------------------------------------------------------- metrics
    def publish_device_memory(self):
        """Gauge the accelerator (or host-RSS fallback) memory stats."""
        if not self.enabled or not getattr(self.config, "memory_metrics",
                                           True):
            return
        stats = device_memory_stats()
        src = stats.pop("source", "none")
        # one canonical label vocabulary: a real backend memory_stats()
        # publishes as source=hbm; the psutil/resource fallbacks keep
        # their host_* names so dashboards can never mistake process RSS
        # for device residency (the autotuner/observatory refuse them).
        label = {"device": "hbm"}.get(src, src)
        for k, v in stats.items():
            self.registry.gauge(f"device_memory_{k}",
                                f"memory stat '{k}'",
                                labels={"source": label}).set(v)

    # ----------------------------------------------------------------- sinks
    # re-serialising the whole trace buffer is O(events); at print cadence
    # on a long run that would stall the train thread. Periodic flushes
    # are therefore throttled (skip if nothing new, at most one export per
    # interval); close()/atexit force the final complete export.
    EXPORT_MIN_INTERVAL_S = 5.0

    def flush(self, force=False):
        if not (self.enabled and self.config.trace):
            return
        import time
        n = self.tracer.event_count()
        if not force:
            if n == self._last_export_n:
                return
            if time.monotonic() - self._last_export_t < \
                    self.EXPORT_MIN_INTERVAL_S:
                return
        self._last_export_n = n
        self._last_export_t = time.monotonic()
        self.tracer.export(self.trace_path)

    def _force_trace_export(self):
        """Anomaly-escalation hook: flush the trace NOW (still subject to
        the flush throttle's 5 s floor between repeated anomalies)."""
        self.flush()

    def close(self):
        if not self.enabled or self._closed:
            return
        self._closed = True
        if self.health is not None:
            self.health.close()
        if self.memory is not None:
            self.memory.close()
        if self.goodput is not None:
            from deepspeed_tpu.telemetry import ledger as _ledger_mod
            self.goodput.close()
            _ledger_mod.reset_ledger(if_current=self.goodput)
        self.flush(force=True)
        _cw.uninstall_global_listener()
        reset_manager(if_current=self)
        atexit.unregister(self.close)


# Process-global manager handle. ``None`` until an enabled
# TelemetryManager installs itself; close() restores None (only if it is
# still the installed one, so a newer engine's manager is not clobbered).
_GLOBAL = None


def get_manager():
    return _GLOBAL


def set_manager(manager):
    """Install *manager* as the process-global handle; returns the old."""
    global _GLOBAL
    old, _GLOBAL = _GLOBAL, manager
    return old


def reset_manager(if_current=None):
    global _GLOBAL
    if if_current is None or _GLOBAL is if_current:
        _GLOBAL = None
