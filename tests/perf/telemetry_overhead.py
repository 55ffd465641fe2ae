"""Telemetry-overhead microbenchmarks (telemetry/).

Asserts:

* the DISABLED ``trace_span`` path — the one every engine step pays
  whether or not telemetry is configured — costs < 2 µs/span (the
  enabled-path cost is reported for reference);
* ``engine.explain_step()`` performs ZERO new XLA compilations (via the
  compile-watch backend-compile counter) when the cost explorer owns the
  step artifact, and the AOT-owning dispatch itself adds no compiles
  across repeated steps;
* with ``cost_explorer`` disabled, the engine carries no census state
  and no explorer gauges — the per-step path is byte-identical to PR-1;
* the ``telemetry.health`` path: enabled, a 20-step run still compiles
  the train step exactly ONCE (the stats variant is selected before the
  first lower, never by signature mutation) and fetches stats only at
  the print cadence; disabled, the step programs and the <2 µs/span
  budget are unchanged (no stats outputs, no monitor, no gauges);
* the ``telemetry.goodput`` ledger: the FULL stack (spans + cost
  explorer + health + goodput) still compiles the train step exactly
  once over 20 steps and fetches device state only at the print
  cadence; the ledger ticks at its cadence only, its categories sum to
  elapsed wall time, the disabled path is inert, and a disabled
  ledger's ``attribute`` costs < 2 µs like the disabled trace_span;
* ``data_prefetch``: a 20-step run through a prefetched deepspeed_io
  loader (host workers + device stage) adds exactly ZERO train-step
  compiles — background placement produces the same avals/shardings —
  and ``engine.close()`` stops every pipeline thread;
* ``comm_overlap``: the bucketed-reduction step variant still compiles
  exactly ONE train-step program over 20 steps, its compiled program
  carries one all-reduce per bucket (not per leaf), and the goodput
  ledger's categories still sum to elapsed;
* ``serving.observability``: the serving observatory is statically
  host-only (no jax import outside its CLI demo — it CANNOT add device
  syncs), an observability-on heterogeneous trace still runs exactly
  ONE compiled decode program with zero retraces and zero extra backend
  compiles, the slot-step ledger's integer categories sum to
  steps x max_batch x decode_steps, and the disabled path is inert;
* ``serving.speculative``: a speculative serving trace with observatory
  AND chronicle armed runs decode through exactly TWO compiled programs
  (one draft, one verify — zero plain-decode signatures), zero
  retraces, zero extra backend compiles in steady state, and the
  slot-step ledger (now carrying ``drafted_rejected``) still sums to
  steps x max_batch x (k+1) exactly;
* ``telemetry.fleet``: the fleet recorder is statically host-only
  outside its CLI demo and the one traced desync builder; with fleet
  shipping AND the desync sentinel armed the train step still compiles
  exactly ONCE over 20 steady-state steps (the checksum is one extra
  program, compiled once at the first tick), windows ship at cadence
  from a background writer that never touches the device, the ledger
  still sums to elapsed, and the DISABLED shipper's note/attribute
  surfaces fit the <2 µs budget;
* ``telemetry.anatomy`` (step-anatomy profiler): engine init never
  imports the xplane parser or the anatomy join (lazy PEP 562 access
  only — pinned both statically over telemetry/__init__.py and live via
  sys.modules after a full engine build), a run that never calls
  ``profile_step`` carries no anatomy state, and ``profile_step`` itself
  adds ZERO new train-step signatures (the capture reuses the primed
  dispatch);
* ``telemetry.server`` (obs server): the scrape endpoint armed AND
  actively hit between steps (/metrics plus every /api/report/* route)
  still compiles the train step exactly ONCE over 20 steps and forces
  no device fetches beyond the health cadence — a scrape reads the
  latest host-side snapshots only; close() releases the port and joins
  the serve thread;
* ``telemetry.slo``: the armed burn monitor is host arithmetic (zero
  extra compiles, per-step evals at a test-tiny interval), a
  seconds-long run can never become burn-eligible against production
  windows (the min-span guard), and the disabled/closed ``tick()``
  paths fit the <2 µs budget;
* ``telemetry.federation``: the fleet aggregator is statically
  host-only (no jax import anywhere in the module) and an ARMED
  federation — the rank announced + actively scraped by the aggregator
  — adds ZERO train-step compiles; with ``jax.device_get`` poisoned
  the aggregator keeps scraping and every merged view still answers
  (a fleet scrape is host HTTP over host snapshots, nothing more);
* the garbage-collection hook (``tracer.watch_gc``): with the tracer not
  live, booking one collection (its start and its stop: two clock reads,
  one liveness check, the counter updates) costs < 2 µs, like a span;
* ``guardian``: an ARMED guardian with no anomalies is free — a 20-step
  run with guardian + health on still compiles the train step exactly
  ONCE (the guardian owns zero compiled programs, statically guarded:
  no jax import module-level outside the demo CLI), the idle ``tick()``
  costs < 2 µs (one attribute read + a truthiness check), and the
  disabled path carries no guardian object and no guardian metrics.

Run manually:  python tests/perf/telemetry_overhead.py [iters] — not
collected by pytest (no test_ prefix), like the other perf scripts here.
"""

import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))
# engine checks need a mesh: force virtual devices BEFORE jax backend init
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=8")
os.environ.setdefault("JAX_PLATFORMS", "cpu")

DISABLED_BUDGET_US = 2.0


def _per_span_us(tracer, iters):
    span = tracer.span   # what a hot loop would hold
    t0 = time.perf_counter()
    for _ in range(iters):
        with span("bench"):
            pass
    return (time.perf_counter() - t0) / iters * 1e6


def _tiny_engine(ce_enabled, health_enabled=False, goodput_enabled=False,
                 prefetch_enabled=False, comm_overlap=False,
                 fleet_enabled=False, guardian_enabled=False,
                 memory_enabled=False, memory_cadence=0,
                 chronicle_enabled=False, server_enabled=False,
                 slo_enabled=False, federation_enabled=False,
                 steps_per_print=10 ** 9):
    import tempfile

    import jax
    jax.config.update("jax_platforms", "cpu")
    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel,
                                           synthetic_batch)
    from deepspeed_tpu.utils import groups
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=512, n_positions=128, n_embd=64,
                     n_layer=2, n_head=4)
    batch = synthetic_batch(8, 64, cfg.vocab_size)
    fleet_cfg = {"enabled": False}
    if fleet_enabled:
        fdir = tempfile.mkdtemp(prefix="ds_fleet_oh_")
        fleet_cfg = {"enabled": True, "run_dir": fdir, "rank": 0,
                     "snapshot_file": os.path.join(fdir,
                                                   "FLEET_HEALTH.json")}
    guardian_cfg = {"enabled": False}
    if guardian_enabled:
        gdir = tempfile.mkdtemp(prefix="ds_guardian_oh_")
        guardian_cfg = {"enabled": True,
                        "journal_file": os.path.join(gdir, "GUARDIAN.json")}
    chronicle_cfg = {"enabled": False}
    if chronicle_enabled:
        cdir = tempfile.mkdtemp(prefix="ds_chron_oh_")
        chronicle_cfg = {
            "enabled": True, "run_dir": os.path.join(cdir, "chronicle"),
            "summary_file": os.path.join(cdir, "CHRONICLE.json"),
            "incidents_file": os.path.join(cdir, "INCIDENTS.json")}
    federation_cfg = {"enabled": False}
    if federation_enabled:
        ddir = tempfile.mkdtemp(prefix="ds_fed_oh_")
        federation_cfg = {
            "enabled": True, "run_dir": os.path.join(ddir, "fleet"),
            "scrape_interval_s": 0.1, "stale_after_s": 5.0,
            "snapshot_file": os.path.join(ddir, "FLEET_CONTROL.json")}
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=GPT2LMHeadModel(cfg),
        config={"train_batch_size": 8,
                "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
                "steps_per_print": steps_per_print,
                "data_prefetch": {"enabled": prefetch_enabled},
                "comm_overlap": {"enabled": comm_overlap,
                                 "bucket_mb": 0.05},
                "guardian": guardian_cfg,
                "telemetry": {"enabled": True, "trace": False,
                              "jsonl": False, "prometheus": False,
                              "cost_explorer": {"enabled": ce_enabled},
                              "health": {"enabled": health_enabled},
                              "goodput": {"enabled": goodput_enabled,
                                          "profiler_capture": False},
                              "memory": {"enabled": memory_enabled,
                                         "cadence": memory_cadence},
                              "chronicle": chronicle_cfg,
                              "server": {"enabled": server_enabled},
                              "slo": {"enabled": slo_enabled,
                                      "eval_interval_s": 0.001},
                              "federation": federation_cfg,
                              "fleet": fleet_cfg}},
        sample_batch=batch)
    return engine, batch


def _backend_compiles(engine):
    reg = engine.telemetry.registry
    return sum(m.value for ms in reg.collect().values() for m in ms
               if m.name == "xla_backend_compiles_total")


def check_explain_step_zero_compiles(steps=4):
    """The compile-watch counter guard: priming + steps + explain_step
    must compile exactly once per program — explain_step itself adds 0."""
    engine, batch = _tiny_engine(ce_enabled=True)
    engine.train_batch(batch=batch)       # primes the owned AOT artifact
    after_prime = _backend_compiles(engine)
    for _ in range(steps):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"AOT-owning dispatch recompiled during steady-state steps: "
        f"{after_prime} -> {after_steps}")
    engine.explain_step()
    engine.explain_step()
    after_explain = _backend_compiles(engine)
    assert after_explain == after_steps, (
        f"explain_step triggered {after_explain - after_steps} XLA "
        f"compilations; it must read the owned artifact only")
    print(f"explain_step XLA compiles: 0 (counter steady at "
          f"{int(after_explain)})")


def check_disabled_path_inert(steps=3):
    """cost_explorer off => no census state, no explorer gauges, no AOT
    wrapper on the step entry points (the PR-1 dispatch, unchanged)."""
    from deepspeed_tpu.runtime.engine import _AOTStep
    engine, batch = _tiny_engine(ce_enabled=False)
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine._cost_census is None
    target = getattr(engine._jit_train, "_compile_watch_target",
                     engine._jit_train)
    assert not isinstance(target, _AOTStep), (
        "disabled cost explorer must not wrap the step entry points")
    snap = engine.telemetry.registry.snapshot()
    for name in ("model_flops_per_step", "hbm_watermark_bytes",
                 "collective_bytes"):
        assert name not in snap, f"unexpected gauge {name} while disabled"
    print("disabled cost-explorer path: no wrapper, no census, no gauges")


def check_health_zero_extra_compiles(steps=20, cadence=5):
    """Acceptance guard: health + cost explorer on, a 20-step run compiles
    the train step exactly once (the stats variant is part of the ONE
    program, selected before first lower) and the host observes stats
    only at the print cadence."""
    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 steps_per_print=cadence)
    assert engine._health_on, "health must be armed on this config"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"health stats variant recompiled mid-run: "
        f"{after_prime} -> {after_steps}")
    mon = engine.telemetry.health
    assert mon.steps_seen == steps
    expected = steps // cadence
    assert mon.samples_seen == expected, (
        f"stats fetched {mon.samples_seen}x over {steps} steps; the "
        f"cadence-{cadence} path must fetch exactly {expected}x — a "
        f"per-step host-device sync crept in")
    snap = engine.telemetry.registry.snapshot()
    assert "train_param_norm" in snap and "train_update_ratio" in snap
    print(f"health path: 1 compile over {steps} steps, "
          f"{mon.samples_seen} cadence fetches, verdict "
          f"{mon.verdict()!r}")


def check_health_disabled_inert(steps=3):
    """health off => no stats outputs, no monitor, no health gauges; the
    step programs are the pre-health ones."""
    engine, batch = _tiny_engine(ce_enabled=False, health_enabled=False)
    assert engine._health_on is False
    assert engine.telemetry.health is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine._pending_health_stats is None
    snap = engine.telemetry.registry.snapshot()
    for name in ("train_param_norm", "train_update_ratio",
                 "train_grad_norm_bucket", "health_nonfinite_buckets",
                 "health_anomalies_total"):
        assert name not in snap, f"unexpected gauge {name} while disabled"
    print("disabled health path: no stats, no monitor, no gauges")


def check_goodput_full_stack_one_compile(steps=20, cadence=5):
    """Acceptance guard: spans + cost explorer + health + goodput ALL
    enabled — still exactly one train-step compile over 20 steps, device
    fetches at the print cadence only, ledger ticks at its cadence only
    (pure host arithmetic), and the category seconds sum to elapsed."""
    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 goodput_enabled=True,
                                 steps_per_print=cadence)
    led = engine._goodput
    assert led is not None, "goodput must be armed on this config"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"full telemetry stack recompiled mid-run: "
        f"{after_prime} -> {after_steps}")
    assert led.steps_seen == steps
    assert led.windows_closed == steps // cadence, (
        f"ledger ticked {led.windows_closed}x over {steps} steps; the "
        f"cadence-{cadence} path must close exactly {steps // cadence} "
        f"windows")
    mon = engine.telemetry.health
    assert mon.samples_seen == steps // cadence, (
        "goodput must not add device fetches beyond the health cadence")
    rep = engine.goodput_report()
    cats = rep["categories_s"]
    drift = abs(sum(cats.values()) - rep["elapsed_s"])
    assert drift <= 0.01 * rep["elapsed_s"] + 1e-6, (
        f"ledger categories sum {sum(cats.values()):.6f}s but elapsed is "
        f"{rep['elapsed_s']:.6f}s")
    snap = engine.telemetry.registry.snapshot()
    assert "goodput_fraction" in snap
    # manager teardown must also uninstall the process-global ledger
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    engine.telemetry.close()
    assert not ledger_mod.get_ledger().enabled, (
        "manager close() must restore the disabled global ledger")
    print(f"goodput full stack: 1 compile over {steps} steps, "
          f"{led.windows_closed} cadence ticks, goodput "
          f"{rep['goodput_fraction']:.2f}, residual drift {drift:.4f}s")


def check_prefetch_zero_extra_compiles(steps=20):
    """Acceptance guard: data_prefetch on (host workers + device stage),
    a 20-step run through a prefetched deepspeed_io loader compiles the
    train step exactly ONCE — pre-placed batches reach the jit with the
    same avals/shardings as main-thread placement — and engine.close()
    (the teardown path) stops every pipeline thread."""
    import threading

    import numpy as np

    from deepspeed_tpu.runtime.dataloader import RepeatingLoader
    from deepspeed_tpu.runtime.prefetch import PrefetchLoader
    engine, batch = _tiny_engine(ce_enabled=True, prefetch_enabled=True)
    rng = np.random.default_rng(0)
    dataset = [{"input_ids": rng.integers(0, 512, (64,), dtype=np.int32)}
               for _ in range(64)]
    loader = engine.deepspeed_io(dataset, num_local_io_workers=2)
    assert isinstance(loader, PrefetchLoader), \
        "data_prefetch on: deepspeed_io must hand back the wrapped loader"
    assert loader.place_fn is not None, \
        "single-process run must arm the device stage"
    it = RepeatingLoader(loader)
    engine.train_batch(data_iter=it)      # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(data_iter=it)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"prefetched dispatch recompiled mid-run: "
        f"{after_prime} -> {after_steps} — the device stage must place "
        f"with the exact shardings the main thread would")
    snap = engine.telemetry.registry.snapshot()
    served = (snap["prefetch_hits_total"][0]["value"]
              + snap["prefetch_misses_total"][0]["value"])
    assert served == steps, f"pipeline served {served} of {steps} pulls"
    alive = [t for t in threading.enumerate()
             if t.is_alive() and t.name.startswith("ds-prefetch")]
    assert alive, "pipeline threads should be live mid-run"
    engine.close()                        # manager close rides along
    deadline = time.perf_counter() + 3.0
    while time.perf_counter() < deadline:
        alive = [t for t in threading.enumerate()
                 if t.is_alive() and t.name.startswith("ds-prefetch")]
        if not alive:
            break
        time.sleep(0.05)
    assert not alive, (f"engine.close() leaked prefetch threads: "
                       f"{[t.name for t in alive]}")
    print(f"prefetch path: 1 compile over {steps} steps, "
          f"{int(snap['prefetch_hits_total'][0]['value'])} hits, "
          f"teardown leak-free")


def check_comm_overlap_zero_extra_compiles(steps=20, cadence=5):
    """PR-10 acceptance guard: the bucketed-reduction (comm_overlap)
    step variant is selected BEFORE the first lower, like health — a
    20-step run still compiles the train step exactly ONCE, and the
    goodput ledger's categories still sum to elapsed wall time (the
    shard_map variant must not confuse the attribution stack)."""
    engine, batch = _tiny_engine(ce_enabled=True, goodput_enabled=True,
                                 comm_overlap=True,
                                 steps_per_print=cadence)
    assert engine._comm_overlap_on, \
        "comm_overlap must be armed on this dp=8 config"
    n_buckets = engine._overlap_spec.n_buckets
    assert 1 < n_buckets < engine._overlap_spec.n_leaves
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"comm_overlap step recompiled mid-run: "
        f"{after_prime} -> {after_steps}")
    ar = engine.get_cost_census().collective_counts.get("all-reduce", 0)
    assert ar <= n_buckets + 2, (
        f"comm_overlap program carries {ar} all-reduces for "
        f"{n_buckets} buckets — the bucketing collapsed nothing")
    rep = engine.goodput_report()
    cats = rep["categories_s"]
    drift = abs(sum(cats.values()) - rep["elapsed_s"])
    assert drift <= 0.01 * rep["elapsed_s"] + 1e-6, (
        f"ledger categories sum {sum(cats.values()):.6f}s but elapsed is "
        f"{rep['elapsed_s']:.6f}s with comm_overlap on")
    snap = engine.telemetry.registry.snapshot()
    assert "comm_overlap_buckets" in snap
    engine.telemetry.close()
    print(f"comm_overlap path: 1 compile over {steps} steps, "
          f"{n_buckets} buckets / {ar} all-reduces, ledger drift "
          f"{drift:.4f}s")


def check_serving_obs_no_device_access():
    """The serving observatory must stay PURE HOST bookkeeping — a module
    that cannot reach jax cannot introduce a per-step device sync. The
    guard is static: no jax import anywhere in the module outside the
    CLI demo functions (which build a real engine on purpose)."""
    import ast

    import deepspeed_tpu.telemetry.serving_observatory as obs_mod
    with open(obs_mod.__file__) as f:
        tree = ast.parse(f.read())

    def jax_imports(node):
        found = []
        for n in ast.walk(node):
            if isinstance(n, ast.Import):
                found += [a.name for a in n.names
                          if a.name.split(".")[0] == "jax"]
            elif isinstance(n, ast.ImportFrom) and \
                    (n.module or "").split(".")[0] == "jax":
                found.append(n.module)
        return found

    offenders = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in ("_demo", "main"):
            continue
        offenders += jax_imports(node)
    assert not offenders, (
        f"serving_observatory imports jax outside its CLI demo "
        f"({offenders}) — the observatory must stay host-only so it "
        f"cannot add device syncs to the serving step")
    print("serving observatory: statically host-only (no jax imports "
          "outside the CLI demo)")


def check_serving_obs_zero_extra_compiles():
    """Acceptance guard: a heterogeneous serving trace with the FULL
    observatory armed (timelines + slot ledger + SLO rules) still runs
    ONE compiled decode program, one prefill program, zero retraces —
    and after the programs exist, a second differently-shaped wave adds
    exactly zero backend compiles. The slot-step ledger's categories sum
    to steps x max_batch x decode_steps exactly (integers, by
    construction)."""
    import tempfile

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.serving.server import ServingEngine
    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry
    from deepspeed_tpu.utils import groups
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=64, n_embd=32,
                     n_layer=2, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    registry = MetricsRegistry()
    snap_path = os.path.join(tempfile.mkdtemp(prefix="ds_srv_obs_"),
                             "SERVING_HEALTH.json")
    srv = ServingEngine(eng, config={
        "max_batch": 3, "block_size": 8, "prefill_chunk": 6,
        "decode_steps": 2,
        "observability": {"enabled": True, "window": 4,
                          "snapshot_file": snap_path}},
        registry=registry)
    assert srv.observatory is not None

    def backend_compiles():
        return sum(m.value for ms in registry.collect().values()
                   for m in ms if m.name == "xla_backend_compiles_total")

    compile_watch.install_global_listener(registry)
    try:
        rng = np.random.default_rng(3)
        for plen, gen in ((9, 5), (3, 7), (17, 4)):     # warm both programs
            srv.submit(rng.integers(0, cfg.vocab_size, (plen,)), gen)
        srv.serve_forever()
        after_warm = backend_compiles()
        for plen, gen in ((13, 6), (2, 3), (27, 8), (5, 5)):
            srv.submit(rng.integers(0, cfg.vocab_size, (plen,)), gen)
        outs = srv.serve_forever()
        assert len(outs) == 4
        assert backend_compiles() == after_warm, (
            "observability-on serving recompiled in steady state — the "
            "observatory must never change program shapes")
    finally:
        compile_watch.uninstall_global_listener()
    stats = srv.compile_stats()
    assert stats == {"decode_signatures": 1, "prefill_signatures": 1,
                     "retraces": 0}, stats
    led = srv.observatory.ledger
    units, steps = led.totals()
    assert sum(units.values()) == steps * led.max_batch * led.K, (
        f"slot-step ledger lost units: {units} over {steps} steps")

    # disabled path: no observatory object, no observatory metrics, the
    # scheduler runs without an observer
    reg2 = MetricsRegistry()
    srv2 = ServingEngine(eng, config={"max_batch": 2, "block_size": 8},
                         registry=reg2)
    assert srv2.observatory is None and srv2.scheduler.observer is None
    srv2.submit(rng.integers(0, cfg.vocab_size, (7,)), 3)
    srv2.serve_forever()
    snap = reg2.snapshot()
    for name in ("serving_slot_units_total", "serving_window_wasted_frac",
                 "serving_anomalies_total", "serving_kv_fragmentation"):
        assert name not in snap, f"unexpected metric {name} while disabled"
    print(f"serving observatory: 1 decode program, 0 retraces, 0 extra "
          f"backend compiles with observability on; ledger "
          f"{sum(units.values())} units == {steps} steps x "
          f"{led.max_batch} x K={led.K}; disabled path inert")


def check_spec_zero_extra_compiles():
    """ISSUE-20 acceptance guard: SPECULATIVE serving with the full
    observability plane armed (observatory + chronicle) runs the decode
    path through exactly TWO compiled programs — one draft, one verify —
    with ZERO retraces and zero plain-decode signatures, and a second
    differently-shaped request wave adds exactly zero backend compiles.
    The slot-step ledger's integer categories (now including
    ``drafted_rejected``) still sum to steps x max_batch x (k+1)
    exactly."""
    import tempfile

    import numpy as np

    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel
    from deepspeed_tpu.serving.server import ServingEngine
    from deepspeed_tpu.telemetry import chronicle as chron_mod
    from deepspeed_tpu.telemetry import compile_watch
    from deepspeed_tpu.telemetry.chronicle import (RunChronicle,
                                                   set_chronicle)
    from deepspeed_tpu.telemetry.metrics import MetricsRegistry
    from deepspeed_tpu.utils import groups
    groups.destroy()
    groups.initialize()
    cfg = GPT2Config(vocab_size=256, n_positions=128, n_embd=32,
                     n_layer=4, n_head=2)
    model = GPT2LMHeadModel(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        {"input_ids": jnp.zeros((1, 8), jnp.int32)})["params"]
    eng = deepspeed_tpu.init_inference(model, params=params,
                                       dtype=jnp.float32)
    registry = MetricsRegistry()
    tmp = tempfile.mkdtemp(prefix="ds_srv_spec_")
    set_chronicle(RunChronicle(run_dir=tmp, enabled=True))
    srv = ServingEngine(eng, config={
        "max_batch": 3, "block_size": 8, "prefill_chunk": 6,
        "speculative": {"enabled": True, "k": 3},
        "observability": {"enabled": True, "window": 4,
                          "snapshot_file": os.path.join(
                              tmp, "SERVING_HEALTH.json")}},
        registry=registry)
    assert srv.speculative is not None and srv.observatory is not None
    assert chron_mod.get_chronicle().enabled, "chronicle must be armed"

    def backend_compiles():
        return sum(m.value for ms in registry.collect().values()
                   for m in ms if m.name == "xla_backend_compiles_total")

    compile_watch.install_global_listener(registry)
    try:
        rng = np.random.default_rng(7)
        for plen, gen in ((9, 8), (3, 12), (17, 6)):    # warm all programs
            srv.submit(rng.integers(0, cfg.vocab_size, (plen,)), gen)
        srv.serve_forever()
        after_warm = backend_compiles()
        spec_steps = 0
        for plen, gen in ((13, 9), (2, 5), (27, 11), (5, 7), (21, 8)):
            srv.submit(rng.integers(0, cfg.vocab_size, (plen,)), gen)
        while srv.scheduler.has_work() and spec_steps < 64:
            srv.step()
            spec_steps += 1
        assert spec_steps >= 20 or not srv.scheduler.has_work(), \
            "trace ended before exercising steady-state speculation"
        assert backend_compiles() == after_warm, (
            "speculative serving recompiled in steady state — draft + "
            "verify must stay two fixed programs")
    finally:
        compile_watch.uninstall_global_listener()
        chron_mod.reset_chronicle()
    stats = srv.compile_stats()
    assert stats == {"decode_signatures": 0, "prefill_signatures": 1,
                     "retraces": 0, "draft_signatures": 1,
                     "verify_signatures": 1}, stats
    led = srv.observatory.ledger
    units, steps = led.totals()
    assert led.K == srv.speculative.k + 1, \
        "the ledger's K basis must be the verify width k+1"
    assert sum(units.values()) == steps * led.max_batch * led.K, (
        f"slot-step ledger lost units under speculation: {units} over "
        f"{steps} steps")
    snap = registry.snapshot()
    drafted = snap["serving_spec_drafted_total"][0]["value"]
    accepted = snap["serving_spec_accepted_total"][0]["value"]
    assert drafted > 0 and 0 < accepted <= drafted, (drafted, accepted)
    srv.close()
    print(f"speculative serving: exactly {{1 draft, 1 verify}} programs, "
          f"0 retraces, 0 extra backend compiles over {steps} armed "
          f"steps; ledger {sum(units.values())} units == {steps} x "
          f"{led.max_batch} x K={led.K}; acceptance "
          f"{accepted / drafted:.0%}")


def check_fleet_zero_extra_compiles(steps=20, cadence=5):
    """ISSUE-11 acceptance guard: the FULL stack (spans + cost explorer
    + health + goodput) with fleet shipping AND the desync sentinel
    armed keeps EXACTLY 1 train-step compile over 20 steady-state steps.
    The desync checksum is its own small program compiled ONCE at the
    first fleet tick (the priming phase below, like the train step's own
    first dispatch); after that, 20 more steps with ticks and checksum
    fetches add zero backend compiles. The shipper thread never touches
    the device (the checksum fetch happens on the main thread at
    cadence, attributed like the health tick) and the ledger's
    categories still sum to elapsed."""
    import threading

    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 goodput_enabled=True, fleet_enabled=True,
                                 steps_per_print=cadence)
    assert engine._fleet is not None, "fleet must be armed"
    assert engine._fleet_monitor is not None
    assert engine._desync_on, "desync must arm on this dp=8 zero=0 config"
    # priming: the train-step compile (step 1), then the first fleet
    # tick (step `cadence`) compiles the desync-checksum program ONCE —
    # plus XLA-CPU's one-time per-(shape,sharding) host-transfer
    # programs for each distinct param layout entering a NEW computation
    # (measured: a plain jit sum over the same tree pays the same tax;
    # every one is cached — the steady-state assertion below is the
    # real guard). Bound it by the leaf count so a per-call leak cannot
    # hide in the priming window.
    import jax as _jax
    n_leaves = len(_jax.tree_util.tree_leaves(engine.state.params))
    engine.train_batch(batch=batch)
    after_train_compile = _backend_compiles(engine)
    for _ in range(cadence - 1):
        engine.train_batch(batch=batch)
    after_prime = _backend_compiles(engine)
    desync_programs = after_prime - after_train_compile
    assert desync_programs <= n_leaves + 2, (
        f"first desync tick compiled {desync_programs} programs for "
        f"{n_leaves} param leaves — more than one checksum program + "
        f"per-layout transfer stubs can explain")
    for _ in range(steps):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"fleet + desync recompiled in steady state: "
        f"{after_prime} -> {after_steps} over {steps} steps")
    expected_windows = (cadence + steps) // cadence
    assert engine._fleet.windows_shipped == expected_windows, (
        f"shipped {engine._fleet.windows_shipped} windows over "
        f"{cadence + steps} steps at cadence {cadence}; expected "
        f"{expected_windows}")
    assert engine._fleet.ship_errors == 0
    rep = engine.goodput_report()
    cats = rep["categories_s"]
    drift = abs(sum(cats.values()) - rep["elapsed_s"])
    assert drift <= 0.01 * rep["elapsed_s"] + 1e-6, (
        f"ledger categories sum {sum(cats.values()):.6f}s but elapsed "
        f"is {rep['elapsed_s']:.6f}s with fleet on")
    frep = engine.fleet_report()
    assert frep["counters"]["desync_checks"] >= 1
    assert frep["counters"]["desync_mismatches"] == 0
    engine.close()
    alive = [t for t in threading.enumerate()
             if t.is_alive() and t.name.startswith("ds-fleet-ship")]
    assert not alive, f"engine.close() leaked shipper threads: {alive}"
    print(f"fleet path: 1 train-step compile over {cadence + steps} "
          f"steps ({int(desync_programs)} one-time desync/transfer "
          f"programs at the first tick, 0 steady-state), "
          f"{expected_windows} windows shipped, "
          f"{frep['counters']['desync_checks']} clean desync checks, "
          f"ledger drift {drift:.4f}s, teardown leak-free")


def check_fleet_disabled_inert(steps=3):
    """fleet off => no shipper/monitor objects, no fleet metrics; a
    DISABLED shipper's note/attribute surfaces fit the same <2 µs budget
    as the disabled tracer (the satellite's 'disabled-path attribute/
    ship cost' criterion)."""
    from deepspeed_tpu.telemetry.fleet import FleetShipper
    engine, batch = _tiny_engine(ce_enabled=False)
    assert engine._fleet is None and engine._fleet_monitor is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine.fleet_report() == {"enabled": False}
    snap = engine.telemetry.registry.snapshot()
    for name in ("fleet_ranks", "fleet_windows_judged_total",
                 "fleet_anomalies_total", "fleet_desync_checks_total"):
        assert name not in snap, f"unexpected metric {name} while disabled"

    disabled = FleetShipper("/nonexistent", rank=0, enabled=False)
    iters = 100_000
    note = disabled.note_step_time
    t0 = time.perf_counter()
    for _ in range(iters):
        note(0.001)
    note_us = (time.perf_counter() - t0) / iters * 1e6
    timer = disabled.time_category
    t0 = time.perf_counter()
    for _ in range(iters):
        with timer("input_wait"):
            pass
    attr_us = (time.perf_counter() - t0) / iters * 1e6
    assert note_us < DISABLED_BUDGET_US and attr_us < DISABLED_BUDGET_US, (
        f"disabled fleet shipper costs note={note_us:.3f} / "
        f"attr={attr_us:.3f} us — over the {DISABLED_BUDGET_US} us budget")
    print(f"disabled fleet path: no shipper, no metrics, "
          f"{note_us:.3f} us/note, {attr_us:.3f} us/attribute")


def check_fleet_no_device_access():
    """The fleet shipper/monitor must stay PURE HOST bookkeeping — the
    same static guard the serving observatory carries: no jax import
    anywhere in telemetry/fleet.py outside the CLI demo and the ONE
    deliberately-traced function (build_desync_checksum_fn, which the
    engine calls on the main thread; the shipper thread can never reach
    it)."""
    import ast

    import deepspeed_tpu.telemetry.fleet as fleet_ast_mod
    with open(fleet_ast_mod.__file__) as f:
        tree = ast.parse(f.read())

    def jax_imports(node):
        found = []
        for n in ast.walk(node):
            if isinstance(n, ast.Import):
                found += [a.name for a in n.names
                          if a.name.split(".")[0] == "jax"]
            elif isinstance(n, ast.ImportFrom) and \
                    (n.module or "").split(".")[0] == "jax":
                found.append(n.module)
        return found

    offenders = []
    for node in tree.body:
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name in ("_demo", "main",
                                  "build_desync_checksum_fn"):
            continue
        offenders += jax_imports(node)
    assert not offenders, (
        f"telemetry/fleet.py imports jax outside its CLI demo / desync "
        f"builder ({offenders}) — the shipper must stay host-only so it "
        f"cannot add device syncs")
    print("fleet recorder: statically host-only (jax only in the CLI "
          "demo and the traced desync builder)")


def check_anatomy_inert(steps=5):
    """ISSUE-15 acceptance guard: the step-anatomy profiler is free
    until asked for. Statically, telemetry/__init__.py must not import
    xplane/step_anatomy at module level; live, a full engine build plus
    a training run must leave both modules out of sys.modules; and when
    ``profile_step`` IS invoked, the capture reuses the primed train-step
    dispatch — zero new compiled signatures, zero backend compiles."""
    import ast

    import deepspeed_tpu.telemetry as tel_mod
    with open(tel_mod.__file__) as f:
        tree = ast.parse(f.read())
    offenders = []
    for node in tree.body:
        mods = []
        if isinstance(node, ast.Import):
            mods = [a.name for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            mods = [node.module or ""]
        offenders += [m for m in mods if m.endswith(".xplane")
                      or m.endswith(".step_anatomy")]
    assert not offenders, (
        f"telemetry/__init__.py eagerly imports {offenders} — the "
        f"anatomy stack must load only when a capture is post-processed")

    for mod in ("deepspeed_tpu.telemetry.xplane",
                "deepspeed_tpu.telemetry.step_anatomy"):
        sys.modules.pop(mod, None)
    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True)
    for _ in range(steps):
        engine.train_batch(batch=batch)
    for mod in ("deepspeed_tpu.telemetry.xplane",
                "deepspeed_tpu.telemetry.step_anatomy"):
        assert mod not in sys.modules, (
            f"{mod} was imported during engine init/steps — the disabled "
            f"anatomy path must never load the parser")

    from deepspeed_tpu.telemetry.ledger import profiler_available
    if not profiler_available():
        print("anatomy path: lazy imports pinned; profiler unavailable, "
              "skipping the capture-compile check")
        return
    before = _backend_compiles(engine)
    report = engine.profile_step(2, batch=batch)
    after = _backend_compiles(engine)
    assert report.get("enabled") is True, report.get("reason")
    assert after == before, (
        f"profile_step added {int(after - before)} backend compiles — "
        f"the capture must reuse the primed step signature")
    wall = report["device_wall_s"]
    total = sum(report["categories_s"].values())
    assert wall > 0 and abs(total - wall) <= 0.01 * wall
    print(f"anatomy path: lazy imports pinned, 0 extra compiles across a "
          f"2-step capture, categories sum to wall "
          f"({total * 1e3:.2f} / {wall * 1e3:.2f} ms)")


def check_memory_zero_extra_compiles(steps=20, cadence=5):
    """ISSUE-16 acceptance guard: the HBM residency observatory ARMED
    (cost explorer feeding it the pre-flight watermark) over a 20-step
    run adds exactly ZERO train-step compiles — the profile fetch is a
    host RPC into the runtime's allocator bookkeeping, never a program
    change — and the monitor observes windows only at the cadence (no
    per-step fetch crept in)."""
    engine, batch = _tiny_engine(ce_enabled=True, memory_enabled=True,
                                 memory_cadence=cadence)
    mon = engine._memory
    assert mon is not None, "memory observatory must be armed"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"armed memory observatory changed compilation: "
        f"{after_prime} -> {after_steps} over {steps} steps — the "
        f"residency fetch must never touch the step programs")
    expected = steps // cadence
    assert mon.windows_seen == expected, (
        f"memory windows observed {mon.windows_seen}x over {steps} "
        f"steps; the cadence-{cadence} path must fetch exactly "
        f"{expected}x — a per-step profile fetch crept in")
    assert mon.last_attribution is not None
    cats = mon.last_attribution["categories"]
    total = mon.last_attribution["live_total_bytes"]
    assert sum(c["bytes"] for c in cats.values()) == total, (
        "category attribution must re-add exactly to the live total")
    assert mon.predicted_bytes and mon.prediction_source, (
        "cost explorer armed — the pre-flight watermark prediction "
        "must be wired into the monitor")
    snap = engine.telemetry.registry.snapshot()
    assert "memory_live_bytes" in snap and "memory_peak_bytes" in snap
    print(f"memory path: 0 extra compiles over {steps} steps, "
          f"{mon.windows_seen} cadence windows, verdict "
          f"{mon.verdict()!r}, drift {mon.drift()}")


def check_memory_disabled_inert(steps=3):
    """memory off (the default) => no monitor object, no memory gauges,
    and the pprof / memory_observatory modules are never imported — the
    disabled path must not even load the parser."""
    for mod in ("deepspeed_tpu.telemetry.pprof",
                "deepspeed_tpu.telemetry.memory_observatory"):
        sys.modules.pop(mod, None)
    engine, batch = _tiny_engine(ce_enabled=False)
    assert engine._memory is None
    assert engine.telemetry.memory is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine.memory_report() == {"enabled": False}
    snap = engine.telemetry.registry.snapshot()
    for name in ("memory_live_bytes", "memory_peak_bytes",
                 "memory_anomalies_total"):
        assert name not in snap, f"unexpected metric {name} while disabled"
    for mod in ("deepspeed_tpu.telemetry.pprof",
                "deepspeed_tpu.telemetry.memory_observatory"):
        assert mod not in sys.modules, (
            f"{mod} was imported during engine init/steps — the disabled "
            f"memory path must never load the parser")
    print("disabled memory path: no monitor, no gauges, parser unloaded")


def check_memory_obs_no_device_access():
    """The memory observatory must stay PURE HOST bookkeeping — the same
    static guard the serving observatory and fleet recorder carry: no
    jax import anywhere in memory_observatory.py outside the CLI demo,
    and none in pprof.py outside ``fetch_device_memory_profile`` (the
    one deliberate jax touchpoint) and the CLI."""
    import ast

    import deepspeed_tpu.telemetry.memory_observatory as mem_mod
    import deepspeed_tpu.telemetry.pprof as pprof_mod

    def jax_imports(node):
        found = []
        for n in ast.walk(node):
            if isinstance(n, ast.Import):
                found += [a.name for a in n.names
                          if a.name.split(".")[0] == "jax"]
            elif isinstance(n, ast.ImportFrom) and \
                    (n.module or "").split(".")[0] == "jax":
                found.append(n.module)
        return found

    for mod, allowed in ((mem_mod, ("_demo", "main")),
                         (pprof_mod, ("fetch_device_memory_profile",
                                      "_main"))):
        with open(mod.__file__) as f:
            tree = ast.parse(f.read())
        offenders = []
        for node in tree.body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and node.name in allowed:
                continue
            offenders += jax_imports(node)
        assert not offenders, (
            f"{os.path.basename(mod.__file__)} imports jax outside "
            f"{allowed} ({offenders}) — the observatory must stay "
            f"host-only so it cannot add device syncs")
    print("memory observatory: statically host-only (jax only in the "
          "CLI demo / profile fetcher)")


def check_obs_server_zero_extra_compiles(steps=20, cadence=5):
    """ISSUE-18 acceptance guard: the obs server ARMED and actively
    scraped mid-run — /metrics plus every /api/report/* route hit
    between steps — still compiles the train step exactly ONCE over 20
    steps, and the request path forces no extra device fetches (the
    health monitor's cadence fetch count is unchanged by the scrapes:
    providers are host-side report() methods, never the engine's
    device-ticking *_report wrappers)."""
    import json as _json
    import urllib.request

    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 goodput_enabled=True, server_enabled=True,
                                 slo_enabled=True, steps_per_print=cadence)
    srv = engine._obs_server
    assert srv is not None, "obs server must be armed on this config"
    assert engine._slo is not None, "slo monitor must be armed"
    routes = ["/metrics", "/healthz", "/readyz", "/api/events"] + [
        f"/api/report/{name}" for name in srv.providers()]
    assert "/api/report/slo" in routes and "/api/report/goodput" in routes

    def scrape_all():
        for route in routes:
            with urllib.request.urlopen(srv.url + route, timeout=5) as r:
                r.read()
                assert r.status == 200, (route, r.status)

    engine.train_batch(batch=batch)       # the one compile
    scrape_all()
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
        scrape_all()
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"scraping the obs server recompiled the step: "
        f"{after_prime} -> {after_steps} over {steps} steps")
    expected = steps // cadence
    assert engine.telemetry.health.samples_seen == expected, (
        f"device stats fetched {engine.telemetry.health.samples_seen}x "
        f"over {steps} scraped steps; the cadence-{cadence} path must "
        f"fetch exactly {expected}x — a scrape forced a device sync")
    with urllib.request.urlopen(srv.url + "/healthz", timeout=5) as r:
        health = _json.loads(r.read())
    assert health["monitors"], "healthz must inventory the armed monitors"
    n_scrapes = srv.report()["requests_total"]
    engine.close()
    # close() must release the port and join the serve thread
    import socket
    import threading
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind((srv.host, srv.port))
    alive = [t for t in threading.enumerate()
             if t.is_alive() and t.name.startswith("ds-obs-server")]
    assert not alive, f"engine.close() leaked obs-server threads: {alive}"
    print(f"obs server path: 1 compile over {steps} scraped steps "
          f"({n_scrapes} requests, {len(routes)} routes), device "
          f"fetches at cadence only, teardown leak-free")


def check_slo_armed_inert(steps=20, cadence=5):
    """SLO monitor ARMED (goodput objective live, production windows) on
    a healthy short run: zero extra train-step compiles (burn math is
    host arithmetic over the ledger's own numbers), every eval stays
    tier-ok (a seconds-long run can never span half a 5-minute window),
    and no burn anomalies fire."""
    engine, batch = _tiny_engine(ce_enabled=True, goodput_enabled=True,
                                 slo_enabled=True, steps_per_print=cadence)
    slo = engine._slo
    assert slo is not None, "slo monitor must be armed on this config"
    assert [o["name"] for o in slo.objectives] == ["training_goodput"]
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"armed slo monitor changed compilation: {after_prime} -> "
        f"{after_steps} over {steps} steps — burn math must stay on "
        f"the host")
    assert slo.evals == steps, (
        f"slo evaluated {slo.evals}x over {steps} steps at a test-tiny "
        f"interval — the per-step tick wiring rotted")
    rep = slo.report()
    obj = rep["objectives"]["training_goodput"]
    assert obj["tier"] == "ok" and rep["rule_counts"] == {}, (
        f"a seconds-long run burned a 5-minute window: {obj}")
    assert not obj["windows"]["fast"]["eligible"], (
        "the min-span eligibility guard rotted — a short run must not "
        "be eligible to burn")
    print(f"slo armed path: 1 compile over {steps} steps, {slo.evals} "
          f"host-side evals, tier ok, 0 anomalies")


def check_slo_disabled_inert(steps=3, iters=100_000):
    """telemetry.slo off (the default) => no monitor object, no slo
    metrics; a DISABLED monitor's tick() and a CLOSED monitor's tick()
    both fit the same <2 µs budget as the disabled tracer."""
    from deepspeed_tpu.telemetry.slo import SloMonitor
    engine, batch = _tiny_engine(ce_enabled=False, goodput_enabled=True)
    assert engine._slo is None and engine._obs_server is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    snap = engine.telemetry.registry.snapshot()
    for name in ("slo_burn_rate", "slo_burn_total",
                 "slo_anomalies_total"):
        assert name not in snap, f"unexpected metric {name} while disabled"

    disabled = SloMonitor(enabled=False)
    tick = disabled.tick
    t0 = time.perf_counter()
    for i in range(iters):
        tick(step=i)
    dis_us = (time.perf_counter() - t0) / iters * 1e6
    closed = SloMonitor(objectives=[{"name": "g", "kind": "goodput",
                                     "target": 0.9}])
    closed.close()
    tick = closed.tick
    t0 = time.perf_counter()
    for i in range(iters):
        tick(step=i)
    closed_us = (time.perf_counter() - t0) / iters * 1e6
    assert dis_us < DISABLED_BUDGET_US and closed_us < DISABLED_BUDGET_US, (
        f"slo tick disabled={dis_us:.3f} / closed={closed_us:.3f} us — "
        f"over the {DISABLED_BUDGET_US} us budget")
    print(f"disabled slo path: no monitor, no metrics, "
          f"{dis_us:.3f} us/disabled-tick, {closed_us:.3f} us/closed-tick")


def check_guardian_armed_zero_overhead(steps=20, cadence=5):
    """ISSUE-13 acceptance guard: guardian ARMED (with health feeding
    it) on a healthy run — still exactly ONE train-step compile over 20
    steady-state steps (the guardian owns zero compiled programs; its
    actions are host-side state swaps through existing engine paths),
    no actions taken, and the armed-idle tick — the cost every step
    pays once the guardian is on — fits the same <2 µs budget as the
    disabled tracer."""
    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 guardian_enabled=True,
                                 steps_per_print=cadence)
    g = engine._guardian
    assert g is not None and g.enabled, "guardian must be armed"
    assert engine.telemetry.health.on_anomaly is not None, \
        "armed guardian must be subscribed to the health hook"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"armed guardian changed compilation: {after_prime} -> "
        f"{after_steps} over {steps} steps — the guardian must own "
        f"zero compiled programs")
    assert not g.actions, (
        f"guardian acted on a healthy run: {g.actions}")
    # armed-idle tick cost: what every post-apply pays while nothing is
    # wrong (the queue is empty, so this is one attr read + truthiness)
    tick = g.tick
    iters = 100_000
    t0 = time.perf_counter()
    for i in range(iters):
        tick(i)
    per_us = (time.perf_counter() - t0) / iters * 1e6
    assert per_us < DISABLED_BUDGET_US, (
        f"armed-idle guardian tick {per_us:.3f} us exceeds the "
        f"{DISABLED_BUDGET_US} us budget")
    engine.close()
    print(f"guardian armed path: 1 compile over {steps} steps, "
          f"0 actions, {per_us:.3f} us/idle-tick")


def check_guardian_disabled_inert(steps=3):
    """guardian off (the default) => no guardian object, no subscribed
    hooks, no guardian metrics."""
    engine, batch = _tiny_engine(ce_enabled=False, health_enabled=True)
    assert engine._guardian is None
    assert engine.telemetry.health.on_anomaly is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine.guardian_report() == {"enabled": False}
    snap = engine.telemetry.registry.snapshot()
    assert "guardian_actions_total" not in snap, \
        "unexpected guardian metric while disabled"
    print("disabled guardian path: no object, no hooks, no metrics")


def check_goodput_disabled_inert(steps=3):
    """goodput off => no ledger object, no goodput metrics, the global
    ledger stays the disabled singleton, and a disabled ledger's
    attribute() fits the same <2 us budget as the disabled tracer."""
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    engine, batch = _tiny_engine(ce_enabled=False)
    assert engine._goodput is None
    for _ in range(steps):
        engine.train_batch(batch=batch)
    assert engine.goodput_report() == {"enabled": False}
    snap = engine.telemetry.registry.snapshot()
    for name in ("goodput_fraction", "goodput_window_fraction",
                 "badput_seconds_total", "goodput_anomalies_total"):
        assert name not in snap, f"unexpected metric {name} while disabled"
    assert not ledger_mod.get_ledger().enabled

    disabled = ledger_mod.GoodputLedger(enabled=False)
    attribute = disabled.attribute
    iters = 100_000
    t0 = time.perf_counter()
    for _ in range(iters):
        with attribute("input_wait"):
            pass
    per_us = (time.perf_counter() - t0) / iters * 1e6
    assert per_us < DISABLED_BUDGET_US, (
        f"disabled ledger attribute {per_us:.3f} us exceeds the "
        f"{DISABLED_BUDGET_US} us budget")
    print(f"disabled goodput path: no ledger, no metrics, "
          f"{per_us:.3f} us/attribute")


def check_chronicle_armed_zero_extra_compiles(steps=20, cadence=5):
    """Chronicle ARMED with every training-side emitter feeding it
    (health anomaly traffic would too, but this is the healthy-run cost)
    — still exactly ONE train-step compile over 20 steady-state steps.
    The chronicle owns zero compiled programs: emits are host-side
    appends, and the correlator runs off-path at report time."""
    from deepspeed_tpu.telemetry import chronicle as chron_mod
    engine, batch = _tiny_engine(ce_enabled=True, health_enabled=True,
                                 goodput_enabled=True,
                                 chronicle_enabled=True,
                                 steps_per_print=cadence)
    chron = engine._chronicle
    assert chron is not None and chron.enabled, "chronicle must be armed"
    assert chron_mod.get_chronicle() is chron, \
        "the engine's chronicle must be the process-global one"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"armed chronicle changed compilation: {after_prime} -> "
        f"{after_steps} over {steps} steps — the chronicle must own "
        f"zero compiled programs")
    events = chron.snapshot_events()
    kinds = {e["kind"] for e in events}
    assert "lifecycle" in kinds and "goodput_window" in kinds, (
        f"armed run emitted no lifecycle/goodput events (kinds={kinds}) "
        f"— the emitter wiring rotted")
    doc = engine.chronicle_report()
    assert doc["incidents"]["incidents"] == [], \
        "a healthy run must correlate into zero incidents"
    engine.close()
    assert not chron_mod.get_chronicle().enabled, \
        "close must detach the global chronicle"
    print(f"chronicle armed path: 1 compile over {steps} steps, "
          f"{len(events)} events, 0 incidents")


def check_chronicle_disabled_emit_under_2us(iters=100_000):
    """telemetry.chronicle off (the default) => the global chronicle is
    the disabled singleton and a hot-path emit through it fits the same
    <2 µs budget as the disabled tracer — monitors can emit
    unconditionally without checking ``enabled`` first."""
    from deepspeed_tpu.telemetry import chronicle as chron_mod
    chron_mod.reset_chronicle()
    chron = chron_mod.get_chronicle()
    assert not chron.enabled
    emit = chron.emit
    t0 = time.perf_counter()
    for i in range(iters):
        emit("anomaly", source="health", step=i, rule="loss_spike")
    per_us = (time.perf_counter() - t0) / iters * 1e6
    assert per_us < DISABLED_BUDGET_US, (
        f"disabled chronicle emit {per_us:.3f} us exceeds the "
        f"{DISABLED_BUDGET_US} us budget")
    assert chron.snapshot_events() == []
    print(f"disabled chronicle path: {per_us:.3f} us/emit, 0 retained")


def check_gc_hook_quiet_under_2us(iters=100_000):
    """A registered loop with the tracer not live: the hook's start and
    stop of one collection (called as the collector calls them) fit the
    disabled span's budget, and book the collection and nothing else."""
    from deepspeed_tpu.telemetry import MetricsRegistry, Tracer, set_tracer
    from deepspeed_tpu.telemetry import tracer as tracer_mod

    class Owner:
        pass

    old = set_tracer(Tracer(enabled=False))
    registry, owner = MetricsRegistry(), Owner()
    handle = tracer_mod.watch_gc("serving", registry, owner)
    hook = tracer_mod._GC_WATCH
    start = {"generation": 0, "collected": 0, "uncollectable": 0}
    stop = dict(start, collected=3)

    def per_collection_us():
        t0 = time.perf_counter()
        for _ in range(iters):
            hook("start", start)
            hook("stop", stop)
        return (time.perf_counter() - t0) / iters * 1e6
    try:
        per_us = min(per_collection_us() for _ in range(3))  # best of 3
        assert tracer_mod.get_tracer().events() == []
    finally:
        handle.close()
        set_tracer(old)
    booked = registry.counter("serving_gc_collections_total",
                              labels={"generation": "0"}).value
    assert booked >= 3 * iters, booked
    assert per_us < DISABLED_BUDGET_US, (
        f"quiet gc hook {per_us:.3f} us/collection exceeds the "
        f"{DISABLED_BUDGET_US} us budget")
    print(f"quiet gc hook: {per_us:.3f} us/collection")


def check_chronicle_writer_books_nothing_into_ledger(events=500):
    """The background stream writer runs under the ledger's
    ``suppress_attribution()`` — shipping events must leave every booked
    goodput category EXACTLY unchanged (the writer's wall time is the
    run's background noise, not train-loop badput)."""
    import tempfile

    from deepspeed_tpu.telemetry import chronicle as chron_mod
    from deepspeed_tpu.telemetry import ledger as ledger_mod
    led = ledger_mod.GoodputLedger(profiler_capture=False)
    prev = ledger_mod.get_ledger()
    ledger_mod.set_ledger(led)
    try:
        with led.attribute("host_dispatch"):
            pass
        before = dict(led.report()["categories_s"])
        run_dir = tempfile.mkdtemp(prefix="ds_chron_writer_")
        chron = chron_mod.RunChronicle(run_dir=run_dir, rank=0,
                                       background=True)
        for i in range(events):
            chron.emit("anomaly", source="health", step=i,
                       rule="loss_spike", severity="watch")
        chron.drain()
        chron.close()
        after = led.report()["categories_s"]
        for cat, booked in before.items():
            if cat == "unattributed":
                continue   # the wall-clock residual grows with time
            assert after[cat] == booked, (
                f"chronicle writer booked into {cat!r}: "
                f"{booked} -> {after[cat]}")
        assert len(chron_mod.load_events(run_dir)) == events
    finally:
        ledger_mod.set_ledger(prev)
        led.close()
    print(f"chronicle writer: {events} events shipped, "
          f"0 s booked into the ledger")


def check_federation_zero_extra_compiles(steps=10, cadence=5):
    """ISSUE-19 acceptance guard: fleet federation ARMED — the rank's
    obs server announced into the peer registry and the aggregator
    scraping it at a test-tiny interval — adds exactly ZERO train-step
    compiles, and a federated scrape can never reach the device: with
    ``jax.device_get`` poisoned, the aggregator must keep scraping OK
    and every merged view (metrics / timeline / status / fleet SLO)
    must still answer from host-side snapshots."""
    import jax

    engine, batch = _tiny_engine(ce_enabled=True, goodput_enabled=True,
                                 chronicle_enabled=True,
                                 server_enabled=True, slo_enabled=True,
                                 federation_enabled=True,
                                 steps_per_print=cadence)
    agg = engine._fleet_aggregator
    assert agg is not None, \
        "the auto policy must arm the aggregator on rank 0"
    assert engine._obs_server.report()["identity"] == {"rank": "0"}, \
        "federated ranks must stamp their scrape with their rank"
    engine.train_batch(batch=batch)       # the one compile
    after_prime = _backend_compiles(engine)
    for _ in range(steps - 1):
        engine.train_batch(batch=batch)
    after_steps = _backend_compiles(engine)
    assert after_steps == after_prime, (
        f"armed federation changed compilation: {after_prime} -> "
        f"{after_steps} over {steps} steps")
    # now poison the device boundary and let the aggregator keep
    # scraping the live plane — a scrape that fetches anything dies here
    orig = jax.device_get

    def poisoned(*a, **k):
        raise AssertionError("a federated scrape touched the device")

    jax.device_get = poisoned
    try:
        scrapes0 = agg.status()["counters"]["scrapes_total"]
        deadline = time.perf_counter() + 15.0
        while time.perf_counter() < deadline:
            if agg.status()["counters"]["scrapes_total"] >= scrapes0 + 3:
                break
            time.sleep(0.05)
        st = agg.status()
        assert st["counters"]["scrapes_total"] >= scrapes0 + 3, (
            f"aggregator stopped scraping under the poisoned device: "
            f"{st['counters']}")
        peers = agg.peers()
        assert peers and peers[0]["status"] == "ok", peers
        text = agg.merged_metrics()
        samples = [ln for ln in text.splitlines()
                   if ln and not ln.startswith("#")]
        assert samples and all("rank=" in ln for ln in samples), (
            "merged scrape carries unlabelled sample lines")
        events = agg.merged_events()
        assert events, "no events merged from the live chronicle"
        agg.fleet_report("slo")
    finally:
        jax.device_get = orig
    after_scrapes = _backend_compiles(engine)
    assert after_scrapes == after_steps, (
        f"federated scraping compiled {after_scrapes - after_steps} "
        f"programs on the scraped rank — a scrape must be host HTTP "
        f"only")
    n_scraped = agg.status()["counters"]["scrapes_total"]
    engine.close()
    print(f"federation path: 1 compile over {steps} steps, "
          f"{n_scraped} device-poisoned scrapes, merged views all "
          f"rank-labelled, 0 extra compiles")


def check_federation_no_device_access():
    """telemetry/federation.py must stay PURE HOST bookkeeping — the
    static guard every observatory carries: no jax import anywhere in
    the module (even the CLI harness builds only obs servers and
    chronicles; the subprocess peers it spawns set JAX_PLATFORMS=cpu
    in their own environment)."""
    import ast

    import deepspeed_tpu.telemetry.federation as fed_ast_mod
    with open(fed_ast_mod.__file__) as f:
        tree = ast.parse(f.read())
    offenders = []
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            offenders += [a.name for a in n.names
                          if a.name.split(".")[0] == "jax"]
        elif isinstance(n, ast.ImportFrom) and \
                (n.module or "").split(".")[0] == "jax":
            offenders.append(n.module)
    assert not offenders, (
        f"telemetry/federation.py imports jax ({offenders}) — the "
        f"aggregator must stay host-only so a fleet scrape cannot add "
        f"device syncs anywhere")
    print("federation: statically host-only (no jax imports at all)")


def main(iters=200_000):
    from deepspeed_tpu.telemetry import Tracer

    disabled = Tracer(enabled=False)
    # warm up, then best-of-3 (one-shot timings jitter with the GC)
    _per_span_us(disabled, 1000)
    disabled_us = min(_per_span_us(disabled, iters) for _ in range(3))

    enabled = Tracer(enabled=True, max_events=iters * 3 + 10_000)
    _per_span_us(enabled, 1000)
    enabled_us = min(_per_span_us(enabled, iters) for _ in range(3))

    print(f"disabled trace_span: {disabled_us:.3f} us/span "
          f"(budget {DISABLED_BUDGET_US} us)")
    print(f"enabled  trace_span: {enabled_us:.3f} us/span")
    assert disabled_us < DISABLED_BUDGET_US, (
        f"disabled tracer overhead {disabled_us:.3f} us/span exceeds the "
        f"{DISABLED_BUDGET_US} us budget — the no-op path regressed")

    check_explain_step_zero_compiles()
    check_disabled_path_inert()
    check_health_zero_extra_compiles()
    check_health_disabled_inert()
    check_goodput_full_stack_one_compile()
    check_goodput_disabled_inert()
    check_prefetch_zero_extra_compiles()
    check_comm_overlap_zero_extra_compiles()
    check_serving_obs_no_device_access()
    check_serving_obs_zero_extra_compiles()
    check_spec_zero_extra_compiles()
    check_fleet_no_device_access()
    check_fleet_zero_extra_compiles()
    check_fleet_disabled_inert()
    check_anatomy_inert()
    check_memory_zero_extra_compiles()
    check_memory_disabled_inert()
    check_memory_obs_no_device_access()
    check_obs_server_zero_extra_compiles()
    check_slo_armed_inert()
    check_slo_disabled_inert()
    check_guardian_armed_zero_overhead()
    check_guardian_disabled_inert()
    check_chronicle_armed_zero_extra_compiles()
    check_chronicle_disabled_emit_under_2us()
    check_gc_hook_quiet_under_2us()
    check_chronicle_writer_books_nothing_into_ledger()
    check_federation_zero_extra_compiles()
    check_federation_no_device_access()
    print("OK")


if __name__ == "__main__":
    main(int(sys.argv[1]) if len(sys.argv) > 1 else 200_000)
