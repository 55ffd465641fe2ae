"""``ds_report`` — environment / op compatibility report.

Rebuild of deepspeed/env_report.py (op compatibility table + version
info). Reports jax/TPU state and native-op build status instead of
torch/CUDA."""

import importlib
import shutil
import subprocess

GREEN = "\033[92m"
RED = "\033[91m"
YELLOW = "\033[93m"
END = "\033[0m"
OKAY = f"{GREEN}[OKAY]{END}"
NO = f"{RED}[NO]{END}"


def op_report():
    from deepspeed_tpu.ops.op_builder.builder import ALL_OPS
    max_dots = 23
    print("-" * 64)
    print("DeepSpeed-TPU native op report")
    print("-" * 64)
    print("op name" + "." * (max_dots - len("op name")) +
          " compatible | built")
    print("-" * 64)
    for name, builder_cls in ALL_OPS.items():
        b = builder_cls()
        compatible = OKAY if b.is_compatible() else NO
        built = OKAY if (b.source_path().exists() and
                         not b.needs_build()) else NO
        print(name + "." * (max_dots - len(name)) +
              f" {compatible}  | {built}")
    # Pallas kernels are always "built" (JIT at trace time)
    for kname in ("flash_attention", "fused_layer_norm", "fused_bias_gelu",
                  "fused_softmax", "fused_adam", "fused_lamb", "quantizer"):
        print(kname + "." * (max_dots - len(kname)) +
              f" {OKAY}  | {OKAY} (pallas)")


def debug_report():
    import jax
    print("-" * 64)
    print("DeepSpeed-TPU general environment info:")
    print("-" * 64)
    rows = [
        ("jax version", jax.__version__),
        ("default backend", jax.default_backend()),
        ("device count", jax.device_count()),
        ("devices", ", ".join(str(d) for d in jax.devices()[:8])),
        ("g++", shutil.which("g++") or "MISSING"),
    ]
    try:
        import flax
        rows.append(("flax version", flax.__version__))
    except ImportError:
        rows.append(("flax version", "MISSING"))
    import deepspeed_tpu
    rows.append(("deepspeed_tpu version",
                 getattr(deepspeed_tpu, "__version__", "0.1")))
    for name, value in rows:
        print(f"{name} {'.' * (30 - len(name))} {value}")


def telemetry_report():
    """Availability of each telemetry backend (telemetry/)."""
    print("-" * 64)
    print("DeepSpeed-TPU telemetry backend report")
    print("-" * 64)
    max_dots = 30

    def row(name, ok, note=""):
        print(name + "." * (max_dots - len(name)) +
              f" {OKAY if ok else NO}" + (f"  {note}" if note else ""))

    # pure-stdlib backends: always available
    row("trace spans (chrome json)", True)
    row("jsonl sink", True)
    row("prometheus text exporter", True)
    row("compile watch (signatures)", True)
    row("health observatory (numerics)", True,
        "(telemetry.health block; HEALTH.json forensics)")
    row("goodput ledger (wall-clock)", True,
        "(telemetry.goodput block; GOODPUT.json forensics)")
    row("async input prefetch", True,
        "(data_prefetch block; host workers + device double-buffering, "
        "multi-process device stage included)")
    try:
        from deepspeed_tpu.runtime.comm_overlap import (
            check_scheduler_flags, overlap_xla_flags)
        import jax as _jax
        backend = _jax.default_backend()
        armed = check_scheduler_flags(backend)
        row("comm overlap (bucketed psum)", True,
            "(comm_overlap block; DS_COMM_OVERLAP=1; latency-hiding "
            + ("flags armed" if armed and overlap_xla_flags(backend)
               else ("no flags needed on " + backend if armed
                     else "flags NOT armed — set XLA_FLAGS at launch"))
            + ")")
    except Exception:
        row("comm overlap (bucketed psum)", False)
    row("serving engine (paged KV)", True,
        "(serving block; continuous batching + chunked prefill + top-p)")
    row("serving observatory", True,
        "(serving.observability block; slot-step ledger + SLO rules -> "
        "SERVING_HEALTH.json)")
    row("serving prefix cache (COW)", True,
        "(serving.prefix_cache block; DS_SERVING_PREFIX_CACHE=1; "
        "refcounted block sharing + copy-on-write forks)")
    row("serving speculative decode", True,
        "(serving.speculative block; DS_SERVING_SPEC=1/0; truncated-layer "
        "self-draft + one-dispatch verify, rejections booked as "
        "drafted_rejected)")
    row("serving router (SLO-aware)", True,
        "(serving.router block; prefix-affinity placement + "
        "ttft_slo_breach failover across replicas)")
    row("fleet flight recorder", True,
        "(telemetry.fleet block; per-rank record shipping + skew/desync "
        "sentinels -> FLEET_HEALTH.json)")
    row("goodput autotuner (2-stage)", True,
        "(autotuning block; compile-time pruning + measured probes -> "
        "TUNE_REPORT.json)")
    row("self-healing guardian", True,
        "(guardian block; anomaly->action policies: emergency ckpt, "
        "rollback, fp16 rescue, admission pause -> GUARDIAN.json)")
    row("run chronicle + incidents", True,
        "(telemetry.chronicle block; DS_TELEMETRY_CHRONICLE=1; one "
        "causal event timeline -> CHRONICLE.json, correlated "
        "root-caused incident chains -> INCIDENTS.json)")
    try:
        from deepspeed_tpu.telemetry.obs_server import get_obs_server
        srv = get_obs_server()
        live = srv is not None and not srv.report().get("closed", True)
        row("mission control (obs server + SLO)", True,
            (f"(telemetry.server block; DS_TELEMETRY_SERVER=1; live at "
             f"{srv.url} with {len(srv.providers())} provider(s))"
             if live else
             "(telemetry.server + telemetry.slo blocks; "
             "DS_TELEMETRY_SERVER=1 / DS_TELEMETRY_SLO=1; /metrics "
             "scrape + /api/report/* + burn-rate paging -> "
             "SLO_REPORT.json; not armed in this process)"))
    except Exception:
        row("mission control (obs server + SLO)", False)
    try:
        from deepspeed_tpu.telemetry.federation import FleetAggregator
        del FleetAggregator
        row("fleet federation (cross-process)", True,
            "(telemetry.federation block; DS_TELEMETRY_FEDERATION=1; "
            "peer registry + aggregator scrape -> /federation/metrics, "
            "/api/fleet/events, fleet SLO burn + cross-rank incidents "
            "-> FLEET_CONTROL.json)")
    except Exception:
        row("fleet federation (cross-process)", False)
    try:
        from deepspeed_tpu.telemetry.ledger import profiler_available
        row("jax.profiler programmatic capture", profiler_available(),
            "(goodput on-anomaly start_trace/stop_trace)")
    except Exception:
        row("jax.profiler programmatic capture", False)
    try:
        from deepspeed_tpu.telemetry.xplane import parse_xspace
        del parse_xspace
        row("step anatomy (xplane parser)", True,
            "(telemetry.anatomy block; engine.profile_step(n) -> "
            "STEP_ANATOMY.json; dependency-free .xplane.pb reader)")
    except Exception:
        row("step anatomy (xplane parser)", False)
    try:
        from deepspeed_tpu.telemetry.pprof import parse_profile
        del parse_profile
        import jax.profiler as _jp
        ok = hasattr(_jp, "device_memory_profile")
        row("memory observatory (pprof)", ok,
            "(telemetry.memory block; DS_TELEMETRY_MEMORY=1; "
            "engine.memory_report -> MEMORY_ANATOMY.json; "
            "dependency-free pprof reader)"
            if ok else "(jax.profiler.device_memory_profile missing)")
    except Exception:
        row("memory observatory (pprof)", False)
    try:
        from jax import monitoring
        row("jax.monitoring listener",
            hasattr(monitoring, "register_event_duration_secs_listener"))
    except Exception:
        row("jax.monitoring listener", False)
    try:
        from jax.profiler import TraceAnnotation  # noqa: F401
        row("jax.profiler annotations", True)
    except Exception:
        row("jax.profiler annotations", False)
    try:
        import jax
        stats = jax.local_devices()[0].memory_stats()
        row("device memory_stats", bool(stats),
            "" if stats else "(backend returns none; host RSS fallback)")
    except Exception:
        row("device memory_stats", False, "(host RSS fallback)")
    row("psutil (host RSS fallback)",
        importlib.util.find_spec("psutil") is not None)
    try:
        import torch.utils.tensorboard  # noqa: F401
        row("tensorboard monitor", True)
    except Exception:
        row("tensorboard monitor", False, "(csv fallback)")


def main():
    op_report()
    debug_report()
    telemetry_report()


def cli_main():
    main()


if __name__ == "__main__":
    main()
