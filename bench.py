"""Benchmark: GPT-2 training throughput through the full engine on one chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}

``vs_baseline`` compares achieved model-FLOPs TFLOPS/chip against the
reference's headline transformer-kernel efficiency claim of 64 TFLOPS/GPU
(docs/_posts/2020-05-28-fastest-bert-training.md:16, BASELINE.md). ``mfu``
is the same number as a fraction of the chip's advertised bf16 peak.

Measures a chip or fails: without an accelerator the script exits
non-zero (no CPU fallback), the MFU peak comes from the ``device_kind``
table in ``telemetry/cost_explorer.py`` (an unknown device is an error, not
a default), every phase that raises fails the run, and the JSON line names
the device it ran on. The compile cache is placed by
``deepspeed_tpu.utils.chip.enable_compile_cache``.

Config via env:
  BENCH_MODEL  gpt2 (default) | gpt2-medium | gpt2-xl
  BENCH_ZERO   ZeRO stage (default 0 for gpt2, 3 for gpt2-xl)
  BENCH_HEALTH  1 (default) rides the telemetry.health stats inside the
                timed step and writes HEALTH_BENCH.json; 0 removes the
                stats epilogue from the compiled program entirely
  BENCH_GOODPUT 1 (default) arms the wall-clock goodput ledger (host-side
                only, no ticks inside the timed loop) and writes
                GOODPUT_BENCH.json; 0 disables it
  BENCH_ANATOMY 0 (default) | 1 profiles 3 post-warmup steps OUTSIDE the
                timed loop with jax.profiler, post-processes the trace
                into measured per-category device seconds
                (ANATOMY_BENCH.json, gitignored) and emits the
                measured-vs-predicted drift in the JSON line
  BENCH_PREFETCH 1 (default) feeds the timed loop through the async input
                pipeline (data_prefetch: host collate workers + device
                double-buffering, runtime/prefetch.py) so the H2D copy
                overlaps the step and BENCH_*.json tracks the overlap via
                the ledger's input_wait fraction; 0 restores the fixed
                pre-placed batch path byte-identically
"""

import json
import os
import time

import jax
import numpy as np

REFERENCE_TFLOPS_PER_GPU = 64.0  # DeepSpeed's best published per-device claim


def main():
    from deepspeed_tpu.utils.chip import (enable_compile_cache,
                                          require_accelerator)
    enable_compile_cache()
    device = require_accelerator()
    from deepspeed_tpu.telemetry.cost_explorer import detect_chip
    chip = detect_chip(jax.devices()[0])
    if chip is None:
        raise SystemExit(
            f"bench: no peak known for device_kind {device['kind']!r}; add "
            "it to telemetry/cost_explorer.py KNOWN_CHIPS (an assumed peak "
            "would make every MFU in the record meaningless)")
    peak_tflops = chip["peak_tflops"]

    import deepspeed_tpu
    from deepspeed_tpu.models.gpt2 import (
        GPT2LMHeadModel, PRESETS, synthetic_batch)
    from deepspeed_tpu.utils import groups

    # (batch, seq, timed steps, default ZeRO stage) per supported model
    bench_shapes = {
        "gpt2": (16, 1024, 20, 0),          # 125M
        "gpt2-medium": (8, 1024, 10, 1),    # 350M
        "gpt2-xl": (4, 1024, 5, 3),         # 1.5B: needs ZeRO-3 (+offload)
        # the reference's 64-TFLOPS headline config: BERT-large MLM,
        # seq 128, (Fused)Lamb (docs/_tutorials/bert-pretraining.md:387)
        "bert-large": (64, 128, 20, 0),
        # BASELINE config #4 (MoE-GPT recipe): GPT-2 small dims, 8 experts
        # top-1 on alternate layers — single-chip ep=1 (experts vmapped)
        "gpt2-moe": (8, 1024, 10, 0),
        # BASELINE config #3's sparse_attn half: BERT-large with the
        # block-sparse Fixed layout (Pallas SDD/softmax/DSD kernels) at
        # the long-seq regime the reference's 10-16x claim targets;
        # block 64 (not the torch default 16) so tiles half-fill the MXU
        "bert-sparse": (4, 2048, 10, 0),
    }
    # default: GPT-2 350M ZeRO-1 (BASELINE.json config #2) — the best
    # measured headline on one chip (125M stage-0 underfills the MXU;
    # larger models exceed this chip's compile/memory limits)
    name = os.environ.get("BENCH_MODEL", "gpt2-medium")
    if name not in bench_shapes:
        raise SystemExit(f"BENCH_MODEL must be one of "
                         f"{sorted(bench_shapes)}, got {name!r}")
    batch_size, seq_len, steps, default_zero = bench_shapes[name]
    zero_stage = int(os.environ.get("BENCH_ZERO", str(default_zero)))
    batch_size = int(os.environ.get("BENCH_BS", str(batch_size)))
    # BENCH_SEQ: long-context rows (flash keeps memory O(seq), so a
    # single chip trains seq >> the preset's 1024)
    seq_len = int(os.environ.get("BENCH_SEQ", str(seq_len)))

    if name in ("bert-large", "bert-sparse"):
        from deepspeed_tpu.models.bert import (PRESETS as BERT_PRESETS,
                                               BertForPreTraining,
                                               synthetic_mlm_batch)
        cfg = BERT_PRESETS["bert-large"]
        import dataclasses as _dc
        if name == "bert-sparse":
            sb = int(os.environ.get("BENCH_SPARSE_BLOCK", "64"))
            # BENCH_SPARSE_WINDOW: local-window tokens (round-5 long-seq
            # rows use window 1024 @ block 128 — the fused kernel's
            # MXU-sized tiling; default 256 keeps the round-4 rows
            # comparable)
            win = int(os.environ.get("BENCH_SPARSE_WINDOW", "256"))
            assert win % sb == 0 and sb <= win, (
                f"BENCH_SPARSE_BLOCK={sb}: must divide the {win}-token "
                "local window (BENCH_SPARSE_WINDOW)")
            cfg = _dc.replace(cfg, sparse_attention_mode="fixed",
                              sparse_block=sb,
                              sparse_num_local_blocks=win // sb,
                              sparse_num_global_blocks=1)
        if seq_len > cfg.max_position_embeddings:
            # widen the position table — otherwise XLA silently clamps
            # out-of-range position gathers and benches a degenerate model
            cfg = _dc.replace(cfg, max_position_embeddings=seq_len)
        if os.environ.get("BENCH_REMAT", "") == "1":
            cfg = _dc.replace(cfg, remat=True)
        model = BertForPreTraining(cfg)
        optimizer = {"type": "Lamb", "params": {"lr": 1e-4, "fused": True}}
        # BENCH_MLM=masked: the reference pretraining data format
        # (max_predictions_per_seq gathered positions) — the MLM head runs
        # on P<<S positions instead of the full sequence
        masked_fmt = os.environ.get("BENCH_MLM", "").lower() == "masked"

        def make_batch(seed):
            return synthetic_mlm_batch(batch_size, seq_len, cfg.vocab_size,
                                       seed=seed,
                                       masked_positions_format=masked_fmt)
    else:
        if name == "gpt2-moe":
            import dataclasses as _dc
            cfg = _dc.replace(PRESETS["gpt2"], moe_num_experts=8,
                              moe_expert_interval=2,
                              moe_k=int(os.environ.get("BENCH_MOE_K", "1")),
                              moe_capacity_factor=float(os.environ.get(
                                  "BENCH_MOE_CF", "1.25")),
                              moe_dispatch_impl=os.environ.get(
                                  "BENCH_MOE_DISPATCH", "scatter"))
        else:
            cfg = PRESETS[name]
        import dataclasses as _dc
        if seq_len > cfg.n_positions:
            cfg = _dc.replace(cfg, n_positions=seq_len)
        if os.environ.get("BENCH_REMAT", "") == "1":
            # activation rematerialisation: longest contexts trade ~30%
            # recompute flops for O(layers) less activation HBM
            cfg = _dc.replace(cfg, remat=True)
        if os.environ.get("BENCH_ATTN_MODE"):
            # e.g. BENCH_ATTN_MODE=sparse:1024/128 — causal block-sparse
            # GPT rows (PERF.md round 5)
            cfg = _dc.replace(
                cfg, attention_mode=os.environ["BENCH_ATTN_MODE"])
        model = GPT2LMHeadModel(cfg)
        optimizer = {"type": "Adam", "params": {"lr": 1e-4}}
        if os.environ.get("BENCH_FUSED_OPT", "") == "1":
            optimizer["params"]["fused"] = True  # Pallas fused-Adam path
        if os.environ.get("BENCH_OPT_SWEEP", "") == "1":
            # whole-state one-sweep Adam (clip+update fused over
            # contiguous flat state — ops/adam fused_adam_sweep)
            optimizer["params"]["sweep"] = True

        def make_batch(seed):
            return synthetic_batch(batch_size, seq_len, cfg.vocab_size,
                                   seed=seed)

    n_layer = getattr(cfg, "n_layer", None) or \
        getattr(cfg, "num_hidden_layers", None)
    width = getattr(cfg, "n_embd", None) or getattr(cfg, "hidden_size", None)
    if not n_layer or not width:
        raise SystemExit(
            f"bench: config {type(cfg).__name__} exposes neither "
            "n_layer/n_embd nor num_hidden_layers/hidden_size; the "
            "attention FLOPs term would silently vanish")

    groups.destroy()
    groups.initialize()
    offload_mode = os.environ.get("BENCH_OFFLOAD", "").lower()
    layered = offload_mode == "layered"
    # Telemetry rides along by default (BENCH_TELEMETRY=0 disables): spans
    # + compile watch + metrics cost ~µs against ms-scale steps, and the
    # artifact answers "why was this bench slow" (retraces, stalls)
    # without a rerun. Files land in telemetry/ next to this script; a
    # summary JSON (TELEMETRY_BENCH.json) is written next to BENCH_*.json.
    telemetry_on = os.environ.get("BENCH_TELEMETRY", "1").lower() in (
        "1", "true", "yes")
    # Health stats ride inside the compiled step (norm reductions over the
    # grad/param trees — a few extra HBM sweeps against a matmul-dominated
    # step). Cadence stays 0 -> steps_per_print (pinned to 1e9 here), so
    # the timed loop NEVER pays a stats fetch; health_report() does one
    # on-demand fetch after the rounds for the HEALTH_BENCH.json artifact.
    health_on = telemetry_on and os.environ.get(
        "BENCH_HEALTH", "1").lower() in ("1", "true", "yes")
    # Goodput ledger: pure host-side wall-clock bookkeeping (a few dict
    # adds per step, no device syncs). Cadence 0 -> steps_per_print
    # (pinned to 1e9), so the timed loop never pays a window tick; the
    # report is forced once after the rounds for GOODPUT_BENCH.json —
    # the true end-to-end denominator (compile + stalls + warmup)
    # behind the steady-state headline number. Profiler capture stays
    # off: an escalation mid-round must not perturb the timed loop.
    goodput_on = telemetry_on and os.environ.get(
        "BENCH_GOODPUT", "1").lower() in ("1", "true", "yes")
    # Async input pipeline: the timed loop pulls batches through a
    # prefetched deepspeed_io loader (host collate workers + the device
    # stage's overlapped device_put) instead of re-feeding one pre-placed
    # batch — a real loader's steady state, with the H2D copy off the
    # critical path. The layered engine keeps its own host loop.
    prefetch_on = (not layered) and os.environ.get(
        "BENCH_PREFETCH", "1").lower() in ("1", "true", "yes")
    # Bucketed gradient-collective overlap (comm_overlap): requested by
    # default; the engine arms it only inside its envelope (dp > 1,
    # zero <= 1, dense grads), so the single-chip headline emits
    # comm_overlap=false and multichip rounds track the bucketing.
    comm_overlap_req = (not layered) and os.environ.get(
        "BENCH_COMM_OVERLAP", "1").lower() in ("1", "true", "yes")
    # Fleet flight recorder (telemetry/fleet.py): OFF by default — the
    # shipper's per-step cost is two clock reads, but the bench headline
    # must stay byte-identical to previous rounds unless asked. When on,
    # the fleet cadence stays 0 -> steps_per_print (pinned to 1e9), so
    # the timed loop never ships or fetches a desync checksum; one
    # forced report after the rounds writes FLEET_BENCH.json.
    fleet_on = telemetry_on and os.environ.get(
        "BENCH_FLEET", "0").lower() in ("1", "true", "yes")
    # Step anatomy (telemetry/step_anatomy.py): OFF by default — the
    # profiler capture runs 3 EXTRA steps after the timed loop (outside
    # it, so the headline is untouched) but jax.profiler's one-time init
    # is seconds of host work. When on, ANATOMY_BENCH.json (gitignored —
    # machine-local measured timings, unlike the committed demo
    # artifact) holds the measured per-category device seconds and the
    # JSON line carries the measured-vs-predicted drift.
    anatomy_on = telemetry_on and (not layered) and os.environ.get(
        "BENCH_ANATOMY", "0").lower() in ("1", "true", "yes")
    # HBM residency observatory (telemetry/memory_observatory.py): OFF by
    # default — the memory cadence stays 0 -> steps_per_print (pinned to
    # 1e9), so the timed loop never fetches a device-memory profile; one
    # forced report after the rounds writes MEMORY_BENCH.json (gitignored
    # — machine-local measured bytes; the committed example is the CLI
    # demo's) and the JSON line carries hbm_peak_bytes + watermark_drift.
    memory_on = telemetry_on and os.environ.get(
        "BENCH_MEMORY", "0").lower() in ("1", "true", "yes")
    bench_dir = os.path.dirname(os.path.abspath(__file__))
    telemetry_dir = os.path.join(bench_dir, "telemetry")
    ds_config = {
        "train_batch_size": batch_size,
        "train_micro_batch_size_per_gpu": batch_size // max(
            1, groups.get_data_parallel_world_size()),
        "steps_per_print": 10 ** 9,
        "optimizer": optimizer,
        "zero_optimization": {"stage": zero_stage},
        "bf16": {"enabled": True},
        "data_prefetch": {"enabled": prefetch_on, "depth": 2},
        "comm_overlap": {"enabled": comm_overlap_req},
        # scalar fan-out fires at steps_per_print cadence, which the
        # bench pins to 1e9 — the jsonl/prom sinks would only ever hold
        # empty/partial data, so keep them off and snapshot the registry
        # into TELEMETRY_BENCH.json instead
        "telemetry": {"enabled": telemetry_on,
                      "output_path": telemetry_dir,
                      "job_name": f"bench_{name}",
                      "jsonl": False, "prometheus": False,
                      # own the compiled step artifact (AOT dispatch) so
                      # the post-bench census/MFU cross-check reads the
                      # program that actually ran — zero extra compiles
                      "cost_explorer": {"enabled": True},
                      "health": {"enabled": health_on},
                      "goodput": {"enabled": goodput_on,
                                  "profiler_capture": False},
                      "fleet": {"enabled": fleet_on,
                                "run_dir": os.path.join(telemetry_dir,
                                                        "fleet_run")},
                      "memory": {"enabled": memory_on}},
    }
    if layered:
        # beyond-HBM training: params streamed from host RAM layer by
        # layer (Zero3OffloadEngine) — the only way 1.5B+ params train on
        # this one chip (PERF.md: monolithic gpt2-xl hard-OOMs at 22.8 GB)
        assert name.startswith("gpt2"), "layered offload bench is GPT-2"
        ds_config["zero_optimization"] = {
            "stage": 3, "offload_param": {"device": "cpu"}}
        from deepspeed_tpu.models.gpt2 import gpt2_offload_layers
        model = gpt2_offload_layers(cfg)
    elif offload_mode in ("1", "true", "yes"):
        ds_config["zero_optimization"]["offload_optimizer"] = {"device": "cpu"}

    init_kw = dict(model=model, config=ds_config, sample_batch=make_batch(0))
    if layered:
        init_kw["input_fn"] = lambda b: b["input_ids"]
    engine, _, _, _ = deepspeed_tpu.initialize(**init_kw)
    if layered:
        st = engine.store
        n_params = sum(h.size for i in range(len(engine.layers))
                       for h in st.host_leaves(i))
    else:
        n_params = sum(x.size for x in jax.tree.leaves(engine.state.params))

    batch = make_batch(1)
    if not layered:
        # stage the batch on device once: a real training loop's loader
        # prefetches, so the timed path should not pay the host->device
        # transfer latency per step
        batch = jax.tree.map(jax.device_put, batch)
        jax.block_until_ready(batch)

    data_iter = None
    if prefetch_on:
        # the real-loader path the staged batch above approximates: per-
        # row synthetic dataset -> deepspeed_io (collate in the host
        # workers) -> device stage device_puts batch N+1 while step N
        # runs. The epoch outlasts every pull the bench makes (3 warm-up
        # steps + 3 rounds of `steps`) — a wrap rebuilds the pipeline, a
        # cold start mid-measurement — while the distinct-batch pool
        # stays small (rows index into it modulo, so memory is 8 batches
        # regardless of epoch length).
        from deepspeed_tpu.runtime.dataloader import RepeatingLoader

        class _RowDataset:
            POOL = 8

            def __init__(self, n_batches):
                self._batches = [
                    jax.tree.map(np.asarray, make_batch(100 + i))
                    for i in range(self.POOL)]
                self._rows = batch_size * n_batches

            def __len__(self):
                return self._rows

            def __getitem__(self, i):
                b, r = divmod(i % (batch_size * self.POOL), batch_size)
                return jax.tree.map(lambda a: a[r], self._batches[b])

        data_iter = RepeatingLoader(engine.deepspeed_io(
            _RowDataset(steps * 3 + 4), num_local_io_workers=2))

    def _feed():
        if data_iter is not None:
            return engine.train_batch(data_iter=data_iter)
        return engine.train_batch(batch=batch)

    # jax.block_until_ready on a value of the final state is the barrier
    # (confirmed against a scalar device_get on the v5e, PERF.md bring-up).
    # The layered engine is a host loop whose train_batch is itself
    # synchronous per layer; its loss is the last value produced.
    _last_loss = [None]

    def _sync():
        jax.block_until_ready(_last_loss[0] if layered
                              else engine.state.step)

    for _ in range(3):          # the compile step + 2 warm-up steps
        _last_loss[0] = _feed()
    _sync()

    # Median of 3 rounds. Each round dispatches `steps` async steps and
    # syncs once, as a training loop does.
    round_step_ms = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(steps):
            _last_loss[0] = _feed()
        _sync()
        round_step_ms.append((time.perf_counter() - t0) / steps * 1e3)

    med_step_ms = float(np.median(round_step_ms))
    dt = med_step_ms * steps / 1e3

    tokens_per_s = batch_size * seq_len * steps / dt
    flops_per_token = 6 * n_params + 12 * n_layer * width * seq_len
    if name == "gpt2-moe":
        # honest MoE accounting: each token routes through k of E experts,
        # so (E - k) expert MLPs per MoE block hold params but do no work
        # for that token (top-1: same per-token flops as the dense model)
        n_moe_blocks = cfg.n_layer // cfg.moe_expert_interval
        expert_mlp = 8 * width * width
        flops_per_token -= 6 * (cfg.moe_num_experts - cfg.moe_k) \
            * expert_mlp * n_moe_blocks
    if name == "bert-sparse":
        # the attention-flops term assumes dense [S, S] scores; scale it
        # by the block layout's density (the whole point of sparse attn)
        from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
            FixedSparsityConfig
        layout = FixedSparsityConfig(
            num_heads=cfg.num_attention_heads, block=cfg.sparse_block,
            num_local_blocks=cfg.sparse_num_local_blocks,
            num_global_blocks=cfg.sparse_num_global_blocks,
        ).make_layout(seq_len)
        density = float(layout.sum()) / layout.size
        flops_per_token -= 12 * n_layer * width * seq_len * (1 - density)
    if (os.environ.get("BENCH_ATTN_MODE", "").startswith("sparse")
            and name not in ("bert-large", "bert-sparse")):
        # causal sparse GPT rows: scale the attention term by the
        # unidirectional layout's density over the FULL [S, S] matrix —
        # conservative vs the dense rows' convention, which counts the
        # full square for causal models too
        from deepspeed_tpu.ops.sparse_attention.fused_kernels import \
            sparse_mode_layout
        layout, _ = sparse_mode_layout(os.environ["BENCH_ATTN_MODE"],
                                       cfg.n_head, seq_len)
        density = float(layout.sum()) / layout.size
        flops_per_token -= 12 * n_layer * width * seq_len * (1 - density)
    if name in ("bert-large", "bert-sparse") and masked_fmt:
        # honest accounting for the gathered-positions MLM head: the tied
        # decoder (V*H) + mlm transform (H*H) only run on P of S tokens,
        # so the 6N-per-token approximation must shed the skipped share
        P = max(1, int(round(seq_len * 0.15)))
        head_params = cfg.padded_vocab * width + width * width
        flops_per_token -= 6 * head_params * (1 - P / seq_len)
    if layered:
        # the layered decomposition UNTIES the LM head from wte, so
        # n_params holds BOTH [V,H] tables — but the wte forward is a
        # gather (~0 flops), not a matmul; shed its 6N share
        flops_per_token -= 6 * cfg.padded_vocab * width
    tflops = tokens_per_s * flops_per_token / 1e12
    n_chips = jax.device_count()
    tflops_per_chip = tflops / n_chips

    # ---- XLA cross-check (telemetry/cost_explorer.py): the analytic
    # flops formula above has per-model adjustments (MoE, sparse, masked
    # MLM) that can silently go stale as models evolve. The compiler's
    # own count of the program that JUST RAN is the ground truth; emit
    # the ratio and warn loudly when they disagree by > 10%.
    mfu_xla = flops_ratio = None
    explain = None
    # telemetry_on gate: without it the engine owns no compiled artifact
    # and explain_step would pay a full duplicate compile of the
    # bench-scale program just for the cross-check
    if not layered and telemetry_on:
        explain = engine.explain_step(step_time_s=med_step_ms / 1e3)
        xla_flops_per_chip = explain["flops_per_step_per_device"]
        analytic_per_chip = (flops_per_token * batch_size * seq_len
                             / n_chips)
        if xla_flops_per_chip and analytic_per_chip:
            flops_ratio = xla_flops_per_chip / analytic_per_chip
            if abs(flops_ratio - 1.0) > 0.10:
                print(f"# WARNING: analytic flops formula disagrees "
                      f"with XLA by {(flops_ratio - 1) * 100:+.1f}% "
                      f"(xla/analytic = {flops_ratio:.3f}) — the "
                      f"per-model adjustments in bench.py may be "
                      f"stale for {name!r}", flush=True)
        mfu_xla = explain.get("mfu")

    # optimizer sweep time at bench scale: the configured optimizer's
    # update (+ the global-norm clip the way the engine composes it) over
    # the engine's REAL state — the ISSUE-10 gap tracker (round-5
    # measured ≈23 ms vs a ~13 ms Adam HBM bound on the headline config).
    optimizer_ms = None
    if not layered:
        import jax.numpy as jnp

        from deepspeed_tpu.runtime import optim as optim_lib
        opt = engine.optimizer
        zgrads = jax.tree.map(jnp.zeros_like, engine.state.params)

        def _opt_step(g, s, p):
            u, s2 = optim_lib.clipped_update(opt, g, s, p, 1e-4)
            return jax.tree.map(jnp.add, p, u), s2

        with engine.mesh:
            f = jax.jit(_opt_step)
            jax.block_until_ready(f(zgrads, engine.state.opt_state,
                                    engine.state.params))      # compile
            t0 = time.perf_counter()
            iters = 10
            for _ in range(iters):
                out = f(zgrads, engine.state.opt_state,
                        engine.state.params)
            jax.block_until_ready(out)
            optimizer_ms = round(
                (time.perf_counter() - t0) / iters * 1e3, 2)
        del zgrads, out

    def _dump(filename, key, report):
        """Write one forensics artifact next to this script; a report
        that says ``enabled: False`` (monitor not armed) writes nothing."""
        if report.get("enabled", True) is False:
            return False
        from deepspeed_tpu.telemetry.health import json_safe
        with open(os.path.join(bench_dir, filename), "w") as f:
            json.dump(json_safe({"bench": name,
                                 "step_time_ms": round(med_step_ms, 1),
                                 key: report}), f, indent=1, default=repr,
                      allow_nan=False)
        return True

    # measured step anatomy: 3 profiled steps AFTER (outside) the timed
    # loop, post-processed into per-category device seconds + the
    # measured-vs-predicted drift against the CostExplorer roofline
    anatomy_drift = None
    if anatomy_on:
        ar = engine.profile_step(3, write=False)
        if _dump("ANATOMY_BENCH.json", "anatomy", ar):
            anatomy_drift = {
                r["category"]: (round(r["drift"], 4)
                                if r["drift"] is not None else None)
                for r in ar.get("measured_vs_predicted", [])}
        else:
            print(f"# anatomy capture skipped: {ar.get('reason')}",
                  flush=True)

    # input-pipeline overlap evidence: the whole-run input_wait share of
    # wall time from the goodput ledger. With prefetch on this tracks the
    # overlap (near zero = the H2D copy and collate hid behind compute);
    # with it off (or the fixed-batch path) it is the serialized cost.
    input_wait_frac = None
    if goodput_on and not layered:
        _gp = engine.goodput_report()
        if _gp.get("enabled", True) is not False and _gp["elapsed_s"]:
            input_wait_frac = round(
                _gp["categories_s"]["input_wait"] / _gp["elapsed_s"], 4)

    # measured HBM residency: one forced profile fetch AFTER (outside)
    # the timed loop, attributed exactly against the engine inventory;
    # the full report lands in MEMORY_BENCH.json, the headline carries
    # the peak + its drift against the cost-explorer pre-flight
    hbm_peak_bytes = None
    watermark_drift = None
    if memory_on and not layered:
        mb = engine.memory_report()
        if _dump("MEMORY_BENCH.json", "memory", mb):
            hbm_peak_bytes = mb["watermark"]["measured_peak_bytes"]
            watermark_drift = mb["watermark"]["drift"]

    print(json.dumps({
        "metric": f"{name} train TFLOPS/chip "
                  f"(bs={batch_size} seq={seq_len} bf16 "
                  + ("zero=3+layered-offload (beyond-HBM)"
                     if layered else f"zero={zero_stage}")
                  + ", full engine)",
        "value": round(tflops_per_chip, 2),
        "unit": "TFLOPS/chip",
        # where the number was taken, as jax reports it, and the peak the
        # MFU divides by (telemetry/cost_explorer.py KNOWN_CHIPS)
        "device": device,
        "peak_tflops": peak_tflops,
        "vs_baseline": round(tflops_per_chip / REFERENCE_TFLOPS_PER_GPU, 3),
        "mfu": round(tflops_per_chip / peak_tflops, 4),
        # XLA-census cross-checks (None for the layered engine / telemetry
        # off): mfu_xla uses the compiler's flop count of the program that
        # ran; flops_xla_vs_analytic near 1.0 validates the analytic formula
        "mfu_xla": mfu_xla,
        "flops_xla_vs_analytic": (round(flops_ratio, 4)
                                  if flops_ratio else None),
        "step_time_ms": round(med_step_ms, 1),
        "tokens_per_s": round(tokens_per_s, 1),
        # evidence that the number is steady state, not one lucky loop:
        # per-round per-step times and their spread
        "round_step_ms": [round(x, 1) for x in round_step_ms],
        "step_ms_stddev": round(float(np.std(round_step_ms)), 2),
        # async input pipeline (BENCH_PREFETCH): whether the timed loop
        # fed through the prefetched loader, and the ledger's whole-run
        # input_wait share tracking the overlap (None without goodput)
        "prefetch": prefetch_on,
        "input_wait_frac": input_wait_frac,
        # bucketed gradient-collective overlap: the EFFECTIVE state (the
        # engine arms it only when dp > 1 and the config is in the
        # envelope), and the optimizer-sweep gap tracker (ISSUE-10:
        # measured ≈23 ms vs the ~13 ms Adam HBM bound)
        "comm_overlap": bool(getattr(engine, "_comm_overlap_on", False)),
        "optimizer_ms": optimizer_ms,
        # fleet flight recorder: whether this round shipped rank-tagged
        # window records (BENCH_FLEET=1; FLEET_BENCH.json holds the
        # aggregated report)
        "fleet": fleet_on,
        # measured-vs-predicted per-category drift from the profiled
        # post-loop steps (BENCH_ANATOMY=1; None when off)
        "anatomy_drift": anatomy_drift,
        # measured HBM residency (BENCH_MEMORY=1; MEMORY_BENCH.json holds
        # the full attribution): peak live device bytes over the run and
        # the drift against the cost-explorer pre-flight watermark
        "hbm_peak_bytes": hbm_peak_bytes,
        "watermark_drift": watermark_drift,
        "claim": None,
    }))

    # telemetry artifact next to this script: where the trace/sink files
    # are + the full metrics snapshot (step-time histogram, compile
    # counts/seconds, retraces, memory) for the perf PRs that follow
    tel = getattr(engine, "telemetry", None)
    if tel is not None and tel.enabled:
        # forensics artifacts BEFORE close (close() finalises the
        # monitors): health verdict + last stats sample, the goodput
        # ledger's wall-clock split (compile vs input vs compute — the
        # end-to-end complement of the steady-state headline), and the
        # fleet recorder's aggregated cross-rank view
        if health_on:
            _dump("HEALTH_BENCH.json", "health", engine.health_report())
        if goodput_on:
            _dump("GOODPUT_BENCH.json", "goodput", engine.goodput_report())
        if fleet_on:
            _dump("FLEET_BENCH.json", "fleet", engine.fleet_report())
        tel.close()   # forces the final complete trace export
        engine.monitor.close()
        summary = {
            "bench": name,
            "trace_json": tel.trace_path,
            "sinks": {type(m).__name__: getattr(m, "path", None)
                      for m in engine.monitor.monitors},
            "metrics": tel.registry.snapshot(),
            # full cost-explorer report (roofline, bound-ness verdict,
            # per-axis collective bytes, HBM watermark) for this run
            "explain": explain,
        }
        with open(os.path.join(bench_dir, "TELEMETRY_BENCH.json"), "w") as f:
            json.dump(summary, f, indent=2, default=repr)

    if data_iter is not None:
        data_iter.loader.close()    # stop the prefetch pipeline threads


if __name__ == "__main__":
    main()
