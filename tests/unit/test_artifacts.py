"""Committed-artifact schema guard.

The repo commits example observability artifacts at its root — EXPLAIN.json
(cost explorer), HEALTH.json (training-health observatory), GOODPUT.json
(wall-clock goodput ledger), SERVING_HEALTH.json (the serving
observatory), FLEET_HEALTH.json (the fleet flight recorder),
TUNE_REPORT.json (the goodput autotuner),
GUARDIAN.json (the self-healing guardian's action journal),
STEP_ANATOMY.json (the step-anatomy profiler's demo report),
NORTHSTAR_AOT.json (the 1.5B x 16-chip
AOT proof) and OVERLAP_BENCH.json (the PR-10 bucketed-collective +
one-sweep-optimizer proof). They are regenerated by
CLIs/scripts, which means they can rot: a stale hand-edited copy, or a
regen that silently wrote zeroed analyses (the PR-2 regen EXPOSED exactly
that — the committed all-reduce count was 7 when the real program had
586). These tests pin each artifact's schema and the sanity floors that
catch a zeroed/stale regen, so drift fails tier-1 instead of shipping.
"""

import json
import os

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _load(name):
    path = os.path.join(ROOT, name)
    if not os.path.isfile(path):
        pytest.fail(f"committed artifact {name} is missing from the repo "
                    f"root — regenerate it (see PERF.md)")
    with open(path) as f:
        # strict: bare Infinity/NaN tokens are a Python-only JSON
        # extension — jq / JSON.parse / Go reject them, and these files
        # exist to be consumed by external tooling (health.json_safe
        # stringifies non-finite floats for exactly this reason)
        return json.load(f, parse_constant=lambda tok: pytest.fail(
            f"{name} contains bare {tok!r} — not valid JSON"))


class TestExplainJson:
    def test_schema_and_sanity(self):
        doc = _load("EXPLAIN.json")
        for key in ("program", "n_devices", "flops_per_step_per_device",
                    "bytes_accessed_per_step", "collectives", "preflight",
                    "verdict", "aot_artifact_owned", "config"):
            assert key in doc, f"EXPLAIN.json lost key {key!r}"
        # a zeroed census (failed analysis committed anyway) must fail
        assert doc["flops_per_step_per_device"] > 0
        assert doc["bytes_accessed_per_step"] > 0
        assert doc["n_devices"] >= 1
        assert doc["verdict"] in ("compute", "memory", "comm", "unknown")
        assert doc["aot_artifact_owned"] is True

    def test_collectives_consistent(self):
        doc = _load("EXPLAIN.json")
        col = doc["collectives"]
        for key in ("counts", "wire_bytes", "bytes_by_axis",
                    "total_wire_bytes"):
            assert key in col
        # the same ops must appear in counts and wire_bytes, and the
        # total must equal the per-op sum (a hand-edit breaks this first)
        assert set(col["counts"]) == set(col["wire_bytes"])
        assert col["total_wire_bytes"] == sum(col["wire_bytes"].values())
        assert all(c > 0 for c in col["counts"].values())

    def test_preflight_fields(self):
        pf = _load("EXPLAIN.json")["preflight"]
        assert pf["hbm_watermark_bytes"] > 0
        if pf.get("hbm_bytes"):
            assert pf["fits"] in (True, False)


class TestHealthJson:
    def test_schema(self):
        doc = _load("HEALTH.json")
        assert doc["schema"] == "deepspeed_tpu.health/1"
        for key in ("verdict", "rules", "bucket_names", "counters",
                    "ewma", "anomalies", "last_sample", "ring"):
            assert key in doc, f"HEALTH.json lost key {key!r}"
        assert doc["verdict"] in ("healthy", "watch", "warning",
                                  "critical", "unknown")
        assert doc["bucket_names"], "bucket names must not be empty"
        assert doc["ring"], "the forensics ring must hold samples"

    def test_samples_carry_the_stats_fields(self):
        doc = _load("HEALTH.json")
        sample = doc["last_sample"]
        for key in ("step", "grad_norm", "param_norm", "update_ratio",
                    "bucket_grad_norms", "nonfinite_buckets", "loss_scale",
                    "good_steps", "hysteresis", "overflow"):
            assert key in sample, f"stats sample lost key {key!r}"
        assert len(sample["bucket_grad_norms"]) == len(doc["bucket_names"])

    def test_anomalies_well_formed_and_provenance_decodable(self):
        doc = _load("HEALTH.json")
        assert doc["anomalies"], (
            "the committed example must demonstrate an anomaly (the demo "
            "injects a non-finite grad) — a clean file is a stale regen")
        for a in doc["anomalies"]:
            assert a["rule"]
            assert a["severity"] in ("critical", "warning", "watch")
            assert isinstance(a["step"], int)
        nf = [a for a in doc["anomalies"] if a["rule"] == "nonfinite_grads"]
        assert nf, "the committed example must carry provenance"
        assert set(nf[0]["buckets"]) <= set(doc["bucket_names"])
        # the provenance buckets must agree with the sample's bitmask
        mask = doc["last_sample"]["nonfinite_buckets"]
        from deepspeed_tpu.telemetry.health import decode_nonfinite_mask
        assert decode_nonfinite_mask(mask, doc["bucket_names"]) == \
            nf[0]["buckets"]

    def test_counters_consistent(self):
        c = _load("HEALTH.json")["counters"]
        assert c["steps_seen"] >= c["samples_seen"] >= 1
        assert c["skipped_steps"] <= c["steps_seen"]
        assert c["max_overflow_streak"] >= c["overflow_streak"]
        assert sum(c["anomaly_counts"].values()) >= 1


class TestGoodputJson:
    def test_schema(self):
        doc = _load("GOODPUT.json")
        assert doc["schema"] == "deepspeed_tpu.goodput/1"
        for key in ("elapsed_s", "categories_s", "goodput_fraction",
                    "verdict", "thresholds", "counters", "profiler",
                    "anomalies", "windows"):
            assert key in doc, f"GOODPUT.json lost key {key!r}"
        from deepspeed_tpu.telemetry.ledger import CATEGORIES
        assert set(doc["categories_s"]) == set(CATEGORIES)

    def test_categories_sum_to_elapsed(self):
        doc = _load("GOODPUT.json")
        total = sum(doc["categories_s"].values())
        assert abs(total - doc["elapsed_s"]) <= \
            0.01 * doc["elapsed_s"] + 1e-6, (
                f"categories sum to {total}s but elapsed is "
                f"{doc['elapsed_s']}s — the residual invariant broke")
        assert doc["categories_s"]["unattributed"] >= -1e-6
        assert 0.0 <= doc["goodput_fraction"] <= 1.0

    def test_windows_internally_consistent(self):
        doc = _load("GOODPUT.json")
        assert doc["windows"], "the per-window ring must hold windows"
        for w in doc["windows"]:
            assert abs(sum(w["categories_s"].values()) - w["dur_s"]) <= \
                0.01 * w["dur_s"] + 1e-6
            assert set(w["categories_s"]) == set(doc["categories_s"])

    def test_demo_stall_attributed_and_verdict_names_it(self):
        # the demo INJECTS an input stall: a committed example whose
        # verdict does not name input_wait is a stale/zeroed regen
        doc = _load("GOODPUT.json")
        assert doc["verdict"]["dominant_badput"] == "input_wait"
        assert doc["verdict"]["status"] == "degraded"
        cats = doc["categories_s"]
        assert cats["input_wait"] > cats["unattributed"]
        # demo-scale provenance floors: the CLI runs >= 16 real engine
        # steps and pays a real XLA compile. A test-sized or
        # hand-authored file (1 step, compile == 0) once overwrote the
        # committed example and PASSED the consistency checks — these
        # floors make that class of clobber fail tier-1
        assert doc["counters"]["steps_seen"] >= 16
        assert cats["compile"] > 0
        assert cats["host_dispatch"] > 0
        stalls = [a for a in doc["anomalies"]
                  if a["rule"] == "input_stall"]
        assert stalls, "the injected stall must fire the input_stall rule"
        assert doc["counters"]["anomaly_counts"]["input_stall"] >= 1

    def test_counters_consistent(self):
        doc = _load("GOODPUT.json")
        c = doc["counters"]
        assert c["steps_seen"] >= 1
        # windows_closed counts CADENCE windows; the ring may also hold
        # forced (report-path) partial windows, and is bounded
        cadence_in_ring = sum(not w.get("forced")
                              for w in doc["windows"])
        assert 1 <= cadence_in_ring <= c["windows_closed"]
        assert c["overflow_steps"] <= c["steps_seen"]
        prof = doc["profiler"]
        assert prof["captures"] <= prof["max_captures"]


class TestPrefetchBenchJson:
    """PREFETCH_BENCH.json — the async-input-pipeline acceptance proof
    (tests/perf/prefetch_overlap.py): same throttled loader, prefetch
    off vs on. The floors encode the acceptance criteria themselves — a
    regen where the overlap stopped working cannot be committed."""

    def test_schema(self):
        doc = _load("PREFETCH_BENCH.json")
        assert doc["schema"] == "deepspeed_tpu.prefetch_bench/1"
        for key in ("scenario", "serial", "prefetch", "speedup"):
            assert key in doc, f"PREFETCH_BENCH.json lost key {key!r}"
        for side in ("serial", "prefetch"):
            for key in ("per_step_ms", "steady_input_wait_frac",
                        "input_stall_count"):
                assert key in doc[side], f"{side} lost key {key!r}"
        assert doc["scenario"]["steps"] >= 10

    def test_overlap_floors(self):
        doc = _load("PREFETCH_BENCH.json")
        serial, prefetch = doc["serial"], doc["prefetch"]
        # materially lower wall-clock per step (acceptance criterion)
        assert prefetch["per_step_ms"] <= serial["per_step_ms"] * 0.8, (
            "prefetch run was not materially faster — the overlap "
            "regressed; do not commit this regen")
        assert abs(doc["speedup"] - serial["per_step_ms"]
                   / prefetch["per_step_ms"]) < 0.01
        # the input_wait fraction collapses...
        assert prefetch["steady_input_wait_frac"] <= \
            serial["steady_input_wait_frac"] * 0.5
        # ...and the PR-4 input_stall rule fires ONLY on the serial run
        assert serial["input_stall_count"] >= 1, (
            "the serial run must trip input_stall — otherwise the "
            "scenario no longer exercises an input-bound loader")
        assert prefetch["input_stall_count"] == 0
        # the pipeline really served every pull: the measured steps plus
        # the one unmeasured compile/warm-up step
        served = (prefetch["prefetch_hits"] or 0) + \
            (prefetch["prefetch_misses"] or 0)
        assert served == doc["scenario"]["steps"] + 1


class TestOverlapBenchJson:
    """OVERLAP_BENCH.json — the PR-10 comm-overlap + one-sweep optimizer
    proof (tests/perf/overlap_bench.py). The measured halves (census
    collapse, per-bucket byte attribution, on-path host cost, optimizer
    A/B + the microbench that explains it) are pinned hard; the overlap
    win itself is a LABELED projection (this host has one core and no
    interconnect), pinned to stay labeled and grounded in the committed
    NORTHSTAR census."""

    def test_schema(self):
        doc = _load("OVERLAP_BENCH.json")
        assert doc["schema"] == "deepspeed_tpu.overlap_bench/1"
        for key in ("scenario", "train_step", "optimizer_sweep",
                    "projected_multichip"):
            assert key in doc, f"OVERLAP_BENCH.json lost key {key!r}"
        ts = doc["train_step"]
        for side in ("off", "on"):
            for key in ("per_step_ms", "all_reduce_ops",
                        "collective_positions"):
                assert key in ts[side], f"train_step.{side} lost {key!r}"
        assert doc["scenario"]["steps"] * doc["scenario"]["rounds"] >= 32

    def test_census_collapse_floors(self):
        ts = _load("OVERLAP_BENCH.json")["train_step"]
        off, on = ts["off"], ts["on"]
        # the whole point: per-leaf grad all-reduces -> per-bucket
        assert on["all_reduce_ops"] * 4 <= off["all_reduce_ops"], (
            "bucketing collapsed nothing — do not commit this regen")
        assert on["all_reduce_ops"] <= on["buckets"] + 2
        assert 1 < on["buckets"] < on["grad_leaves"]
        # per-bucket byte attribution: every spec bucket appears as a
        # same-size all-reduce result in the compiled program
        got = list(on["all_reduce_result_bytes"])
        for b in on["bucket_bytes"]:
            assert b in got, (
                f"bucket of {b} B has no matching all-reduce result")
            got.remove(b)
        # not tail-clustered in the instruction stream
        assert on["collective_positions"]["first"] < 0.9
        # the restructuring must not tax the step on this host
        assert ts["on_vs_off"] <= 1.10, (
            "overlap-on taxes the measured step > 10% — the "
            "restructuring regressed; do not commit this regen")

    def test_optimizer_sweep_fields(self):
        opt = _load("OVERLAP_BENCH.json")["optimizer_sweep"]
        mc = opt["measured_cpu"]
        assert mc["unfused_adam_plus_clip_ms"] > 0
        assert mc["fused_sweep_ms"] > 0
        if not mc["sweep_wins"]:
            # the committed artifact must carry the measured REASON: the
            # host's flat-loop bandwidth deficit (PERF.md walkthrough)
            mb = mc["microbench"]
            assert mb["flat_chain_ms"] > mb["tree_chain_ms"], (
                "sweep lost without the flat-loop deficit to explain it")
        proj = opt["projected_v5e_roofline"]
        assert "projection" in proj["note"]
        assert proj["fused_sweep_ms"] < proj["unfused_clip_adam_cast_ms"]
        # the projected sweep must close toward the documented bound
        assert proj["fused_sweep_ms"] <= proj["adam_hbm_bound_ms"] * 1.1

    def test_projection_labeled_and_grounded(self):
        proj = _load("OVERLAP_BENCH.json")["projected_multichip"]
        assert "projection, not a measurement" in proj["note"]
        assert proj["source"] == "NORTHSTAR_AOT.json"
        # grounded in the committed census, not invented numbers
        ns = _load("NORTHSTAR_AOT.json")
        assert proj["all_reduce_ops_off"] == \
            ns["collectives"]["all-reduce"]
        assert proj["projected_speedup"] > 1.0
        collapse = proj["measured_cpu_bucket_collapse"]
        off_ops, on_ops = collapse["off_ops_to_on_ops"]
        assert on_ops * 4 <= off_ops


class TestCkptBenchJson:
    """CKPT_BENCH.json — the async-checkpointing acceptance proof
    (tests/perf/ckpt_bench.py): the same train-and-save loop with
    ``checkpoint.async_save`` off vs on. The floors encode the ISSUE-7
    acceptance criteria — a regen where the snapshot-then-persist
    overlap stopped winning, wrote different bytes, or broke the
    ledger's sum-to-elapsed invariant cannot be committed."""

    def test_schema(self):
        doc = _load("CKPT_BENCH.json")
        assert doc["schema"] == "deepspeed_tpu.ckpt_bench/1"
        for key in ("scenario", "sync", "async", "stall_ratio"):
            assert key in doc, f"CKPT_BENCH.json lost key {key!r}"
        for side in ("sync", "async"):
            for key in ("train_loop_stall_s", "stall_per_save_ms",
                        "ledger_checkpoint_save_s",
                        "ledger_checkpoint_save_frac",
                        "ledger_categories_sum_err_frac", "write_bytes"):
                assert key in doc[side], f"{side} lost key {key!r}"
        assert doc["scenario"]["saves"] >= 3

    def test_async_stalls_5x_less_at_equal_bytes(self):
        doc = _load("CKPT_BENCH.json")
        sync, async_ = doc["sync"], doc["async"]
        # the acceptance criterion itself
        assert doc["stall_ratio"] >= 5.0, (
            "async save no longer stalls the train loop >= 5x less than "
            "sync — the snapshot-then-persist overlap regressed; do not "
            "commit this regen")
        assert abs(doc["stall_ratio"] - sync["train_loop_stall_s"]
                   / async_["train_loop_stall_s"]) < 0.01
        # equal checkpoint bytes: the comparison is apples-to-apples
        assert abs(sync["write_bytes"] - async_["write_bytes"]) <= \
            0.01 * sync["write_bytes"]
        assert sync["write_bytes"] > 10_000_000, (
            "a toy-sized checkpoint can't demonstrate the stall")

    def test_ledger_books_only_the_snapshot(self):
        doc = _load("CKPT_BENCH.json")
        sync, async_ = doc["sync"], doc["async"]
        # sync books ~the whole save as checkpoint_save badput...
        assert sync["ledger_checkpoint_save_s"] >= \
            0.8 * sync["train_loop_stall_s"]
        # ...async shrinks to ~the snapshot time (the suppressed
        # background persist books nothing)
        assert async_["ledger_checkpoint_save_s"] <= \
            sync["ledger_checkpoint_save_s"] / 3
        assert async_["ledger_checkpoint_save_frac"] < \
            sync["ledger_checkpoint_save_frac"]
        # the residual invariant survived the background thread
        for side in (sync, async_):
            assert side["ledger_categories_sum_err_frac"] <= 0.01


class TestServingBenchJson:
    """SERVING_BENCH.json — the continuous-batching acceptance proof
    (tests/perf/serving_bench.py): one mixed-length request trace through
    the batch-synchronous ``generate()`` baseline and the paged-KV
    ServingEngine at equal max batch. The floors encode the acceptance
    criteria — a regen where continuous batching stopped winning, or
    where the decode step stopped being ONE compiled program, cannot be
    committed."""

    def test_schema(self):
        doc = _load("SERVING_BENCH.json")
        assert doc["schema"] == "deepspeed_tpu.serving_bench/4"
        for key in ("scenario", "baseline", "serving", "speedup",
                    "prefix_cache", "router", "speculative"):
            assert key in doc, f"SERVING_BENCH.json lost key {key!r}"
        sc = doc["scenario"]
        for key in ("n_requests", "max_batch", "block_size",
                    "prefill_chunk", "max_model_len", "prompt_len_range",
                    "gen_len_range", "useful_tokens"):
            assert key in sc, f"scenario lost key {key!r}"
        # a mixed-length trace is the point: degenerate uniform traces
        # can't demonstrate head-of-line blocking
        assert sc["n_requests"] >= 24
        assert sc["gen_len_range"][1] >= 4 * sc["gen_len_range"][0]
        assert sc["useful_tokens"] >= 500

    def test_continuous_batching_wins(self):
        doc = _load("SERVING_BENCH.json")
        base, srv = doc["baseline"], doc["serving"]
        # the acceptance criterion itself: strictly higher aggregate
        # tok/s at equal max-batch on the mixed trace
        assert srv["tok_s"] > base["tok_s"], (
            "continuous batching lost to the batch-synchronous baseline "
            "— do not commit this regen")
        assert abs(doc["speedup"] - srv["tok_s"] / base["tok_s"]) < 0.01
        # the baseline must actually pay head-of-line padding (otherwise
        # the scenario no longer exercises what serving fixes)
        assert base["wasted_decode_frac"] >= 0.3
        # burst-arrival TTFT improves too: tokens stream out instead of
        # arriving at batch completion
        assert srv["ttft_ms"]["p50"] < base["ttft_ms"]["p50"]

    def test_one_compiled_decode_program(self):
        srv = _load("SERVING_BENCH.json")["serving"]
        assert srv["compile"] == {"decode_signatures": 1,
                                  "prefill_signatures": 1,
                                  "retraces": 0}, (
            "the whole heterogeneous trace must run through ONE compiled "
            "decode-step program (and one prefill program)")

    def test_latency_and_occupancy_present(self):
        srv = _load("SERVING_BENCH.json")["serving"]
        assert srv["ttft_ms"]["p99"] >= srv["ttft_ms"]["p50"] > 0
        assert srv["token_latency_ms"]["p99"] >= \
            srv["token_latency_ms"]["p50"] > 0
        occ = srv["kv_occupancy"]
        assert 0 < occ["mean"] <= occ["peak"] <= 1.0
        assert srv["decode_steps"] >= 1 and srv["prefill_chunks"] >= 1

    def test_slot_step_attribution(self):
        """The serving-observatory ledger rides the timed trace: integer
        categories summing EXACTLY to steps x max_batch x decode_steps
        (by construction — any drift is an accounting bug, not noise),
        decode_useful equal to the trace's useful tokens, and a wasted
        fraction well below the static baseline's measured waste."""
        doc = _load("SERVING_BENCH.json")
        ss = doc["serving"]["slot_steps"]
        from deepspeed_tpu.telemetry.serving_observatory import \
            SLOT_CATEGORIES
        assert set(ss["units"]) == set(SLOT_CATEGORIES)
        total = sum(ss["units"].values())
        assert total == ss["total_units"] == \
            ss["steps"] * ss["max_batch"] * ss["decode_steps"], (
                "slot-step categories no longer sum to "
                "steps x max_batch x K — do not commit this regen")
        # every USEFUL token of the trace is exactly one decode unit
        assert ss["units"]["decode_useful"] == \
            doc["scenario"]["useful_tokens"]
        wasted = sum(ss["units"][c]
                     for c in ("idle", "frozen", "recompute")) / total
        assert abs(ss["wasted_frac"] - wasted) < 1e-3
        # the acceptance floor: continuous batching must waste LESS than
        # the static baseline it replaced (0.76 measured)
        assert ss["wasted_frac"] < doc["baseline"]["wasted_decode_frac"]

    def test_prefix_cache_reuse_wins(self):
        """The shared-prefix acceptance proof: on the 90%-reuse trace the
        block-level prefix cache must hit at least half its block
        lookups, cut TTFT p50 by >= 1.3x against the cache-off A/B arm,
        keep the decode step ONE compiled program, and keep both arms'
        slot-step ledgers exactly summing. A regen that misses any floor
        cannot be committed."""
        doc = _load("SERVING_BENCH.json")
        pfx = doc["prefix_cache"]
        tr = pfx["trace"]
        # the trace must actually contain reusable structure
        assert tr["reuse_ratio"] >= 0.8 and tr["n_requests"] >= 32
        assert pfx["hit_rate"] >= 0.5, (
            "prefix-cache hit rate fell below the 0.5 acceptance floor "
            "— do not commit this regen")
        assert pfx["hits"] > 0 and pfx["misses"] > 0
        assert abs(pfx["hit_rate"]
                   - pfx["hits"] / (pfx["hits"] + pfx["misses"])) < 1e-3
        assert pfx["ttft_improvement"] >= 1.3, (
            "cached TTFT p50 no longer beats cache-off by 1.3x "
            "— do not commit this regen")
        p50 = pfx["ttft_p50_ms"]
        assert abs(pfx["ttft_improvement"]
                   - p50["cache_off"] / p50["cache_on"]) < 0.01
        # reuse must skip real prefill work, not just relabel it
        assert pfx["prefill_chunks"]["cache_on"] < \
            pfx["prefill_chunks"]["cache_off"]
        assert pfx["blocks_shared_peak"] >= 2
        # sharing must not cost a compiled program or a retrace
        assert pfx["compile"] == {"decode_signatures": 1,
                                  "prefill_signatures": 1, "retraces": 0}
        # the cached_prefill category keeps both arms' ledgers exact
        for arm in ("cache_off", "cache_on"):
            ss = pfx["slot_steps"][arm]
            assert ss["sums_exact"] is True, (
                f"{arm} slot-step ledger no longer sums exactly")
        assert pfx["slot_steps"]["cache_on"]["units"]["cached_prefill"] \
            > 0

    def test_router_scales_replicas(self):
        """The router section records aggregate tok/s vs replica count
        over the same shared-prefix trace, with every request routed and
        placement spread across replicas at N=2."""
        doc = _load("SERVING_BENCH.json")
        rt = doc["router"]
        assert rt["trace_requests"] >= 16
        replicas_seen = []
        for run in rt["runs"]:
            assert sum(run["routed_by_replica"]) == rt["trace_requests"]
            assert run["aggregate_tok_s"] > 0
            assert len(run["routed_by_replica"]) == run["replicas"]
            replicas_seen.append(run["replicas"])
        assert replicas_seen == sorted(set(replicas_seen)) and \
            max(replicas_seen) >= 2
        multi = next(r for r in rt["runs"] if r["replicas"] == 2)
        # affinity concentrates prefixes but never starves a replica
        assert all(n > 0 for n in multi["routed_by_replica"])

    def test_speculative_wins_at_small_batch(self):
        """The speculative acceptance proof: spec-on must beat spec-off
        by the measured >= 1.5x floor at BOTH small batch sizes (the
        ISSUE-20 target regime is bs <= 4), with the off arm running the
        plain multi-token scan at identical tokens-per-dispatch (k+1) so
        the win is layer work saved, not dispatch accounting. Greedy
        parity is lossless by construction and the artifact records it."""
        doc = _load("SERVING_BENCH.json")
        spec = doc["speculative"]
        cfg = spec["config"]
        assert cfg["k"] >= 2 and cfg["acceptance"] == "exact"
        assert cfg["spec_off_decode_steps"] == cfg["k"] + 1
        assert 1 <= cfg["draft_layers"] < cfg["model"]["n_layer"]
        batches = sorted(r["max_batch"] for r in spec["runs"])
        assert batches == [1, 4], "the A/B must cover bs in {1, 4}"
        for run in spec["runs"]:
            assert run["speedup"] >= 1.5, (
                f"speculation gave {run['speedup']}x at "
                f"max_batch={run['max_batch']} — below the 1.5x "
                "acceptance floor; do not commit this regen")
            assert abs(run["speedup"] - run["tok_s"]["spec_on"]
                       / run["tok_s"]["spec_off"]) < 0.01
            assert run["greedy_parity"] is True, (
                "speculative decode diverged from the plain greedy "
                "stream — lossless acceptance broke")
            assert run["useful_tokens"] >= 256

    def test_speculative_acceptance_and_rejection_booked(self):
        """Acceptance counters and the drafted_rejected ledger category
        must both be live: acceptance_rate > 0 and consistent with the
        drafted/accepted counters, and at least one run must book real
        rejections (a draft that never misses demonstrates nothing
        about rejection cost)."""
        doc = _load("SERVING_BENCH.json")
        runs = doc["speculative"]["runs"]
        for run in runs:
            assert run["drafted"] > 0
            assert run["accepted"] + run["rejected"] == run["drafted"]
            assert 0 < run["acceptance_rate"] <= 1.0
            assert abs(run["acceptance_rate"]
                       - run["accepted"] / run["drafted"]) < 1e-3
            # ledger units agree with the registry counter
            assert run["drafted_rejected_units"] == run["rejected"]
        assert any(run["rejected"] > 0 for run in runs), (
            "no drafted token was ever rejected — the committed example "
            "must demonstrate speculation cost being booked")

    def test_speculative_two_programs_exact_sums(self):
        """Steady state is exactly {1 draft, 1 verify} compiled programs
        with ZERO decode signatures and zero retraces on the spec-on
        arm, and both arms' slot-step ledgers keep the sums-exact
        invariant with the new drafted_rejected category present."""
        doc = _load("SERVING_BENCH.json")
        from deepspeed_tpu.telemetry.serving_observatory import \
            SLOT_CATEGORIES
        for run in doc["speculative"]["runs"]:
            assert run["compile"]["spec_on"] == {
                "decode_signatures": 0, "prefill_signatures": 1,
                "retraces": 0, "draft_signatures": 1,
                "verify_signatures": 1}
            assert run["compile"]["spec_off"] == {
                "decode_signatures": 1, "prefill_signatures": 1,
                "retraces": 0}
            for arm in ("spec_off", "spec_on"):
                ss = run["slot_steps"][arm]
                assert set(ss["units"]) == set(SLOT_CATEGORIES)
                assert sum(ss["units"].values()) == ss["total_units"] \
                    == ss["steps"] * ss["max_batch"] * ss["decode_steps"]
            # both arms dispatch the same tokens per slot-step
            assert run["slot_steps"]["spec_off"]["decode_steps"] == \
                run["slot_steps"]["spec_on"]["decode_steps"]
            assert run["slot_steps"]["spec_off"]["units"][
                "drafted_rejected"] == 0


class TestServingHealthJson:
    """SERVING_HEALTH.json — the serving-observatory forensics example
    (``python -m deepspeed_tpu.telemetry.serving_observatory --demo``: a
    tiny engine, an undersized KV pool forcing eviction/recompute churn,
    and a sub-millisecond TTFT SLO nothing can meet). The pins enforce
    the by-construction slot-step invariant and demo-scale floors, so
    neither a hand-edit nor a unit-test-sized accidental write can pass
    as the committed example."""

    def test_schema(self):
        doc = _load("SERVING_HEALTH.json")
        assert doc["schema"] == "deepspeed_tpu.serving_health/3"
        for key in ("verdict", "rules", "slot_ledger", "counters",
                    "anomalies", "windows", "timelines", "engine_state",
                    "queue", "kv"):
            assert key in doc, f"SERVING_HEALTH.json lost key {key!r}"
        assert doc["verdict"] in ("healthy", "watch", "warning",
                                  "critical", "unknown")

    def test_slot_ledger_sums_exactly(self):
        led = _load("SERVING_HEALTH.json")["slot_ledger"]
        from deepspeed_tpu.telemetry.serving_observatory import \
            SLOT_CATEGORIES
        assert set(led["units"]) == set(SLOT_CATEGORIES)
        total = sum(led["units"].values())
        # EXACT equality — the categories are integer micro-units and
        # every scheduler step books max_batch x decode_steps of them
        assert total == led["total_units"] == \
            led["steps"] * led["max_batch"] * led["decode_steps"]
        assert all(isinstance(v, int) and v >= 0
                   for v in led["units"].values())
        assert led["units"]["decode_useful"] > 0
        # the demo's undersized pool must show recompute churn
        assert led["units"]["recompute"] > 0

    def test_windows_internally_exact(self):
        doc = _load("SERVING_HEALTH.json")
        led = doc["slot_ledger"]
        windows = [w for w in doc["windows"] if not w.get("forced")]
        assert windows, "the ring must hold cadence windows"
        for w in windows:
            assert sum(w["slot_units"].values()) == \
                w["steps"] * led["max_batch"] * led["decode_steps"]
            assert 0.0 <= w["wasted_frac"] <= 1.0

    def test_windows_partition_the_ledger(self):
        """The ring's windows partition the step stream: indexes are
        contiguous (an escalation snapshot must not re-append the window
        it just closed as a forced duplicate) and the per-category unit
        sums re-add to the ledger EXACTLY — a consumer summing the ring
        sees the same totals the ledger reports."""
        doc = _load("SERVING_HEALTH.json")
        wins = doc["windows"]
        assert [w["index"] for w in wins] == list(range(len(wins)))
        led = doc["slot_ledger"]
        assert sum(w["steps"] for w in wins) == led["steps"]
        for c in led["units"]:
            assert sum(w["slot_units"][c] for w in wins) == \
                led["units"][c], f"windows over/under-count {c!r}"

    def test_anomalies_fired(self):
        doc = _load("SERVING_HEALTH.json")
        assert doc["anomalies"], (
            "the committed example must demonstrate the SLO rules firing "
            "(the demo's TTFT SLO is unmeetable) — a clean file is a "
            "stale regen")
        from deepspeed_tpu.telemetry.serving_observatory import \
            RULE_SEVERITY
        for a in doc["anomalies"]:
            assert a["rule"] in RULE_SEVERITY
            assert a["severity"] == RULE_SEVERITY[a["rule"]]
            assert isinstance(a["step"], int)
        assert "ttft_slo_breach" in doc["counters"]["anomaly_counts"]

    def test_preemption_cost_visible(self):
        c = _load("SERVING_HEALTH.json")["counters"]
        # the demo pool is undersized ON PURPOSE: eviction happened, and
        # its cost (recompute tokens) is booked, not just its count
        assert sum(c["preemptions_by_reason"].values()) >= 1
        assert c["recompute_tokens"] > 0
        assert set(c["preemptions_by_reason"]) <= {"capacity_growth",
                                                   "admission"}

    def test_demo_scale_floors_and_counters(self):
        doc = _load("SERVING_HEALTH.json")
        c = doc["counters"]
        # demo-scale floors: a test-sized file can never pass as the
        # committed example (the PR-4 GOODPUT clobber lesson)
        assert doc["slot_ledger"]["steps"] >= 32
        assert c["requests_submitted"] >= 8
        assert c["steps_seen"] == doc["slot_ledger"]["steps"]
        assert sum(c["requests_finished"].values()) == \
            c["requests_submitted"], "the demo trace must fully drain"
        assert c["tokens_delivered"] > 0
        assert c["first_tokens"] == c["requests_submitted"]

    def test_timelines_cover_the_lifecycle(self):
        doc = _load("SERVING_HEALTH.json")
        recent = doc["timelines"]["recent"]
        assert len(recent) >= 8
        for tl in recent:
            names = [e["event"] for e in tl["events"]]
            assert names[0] == "queued" and names[-1] == "finished"
            ts = [e["t_ms"] for e in tl["events"]]
            assert ts == sorted(ts)
        # the preemption churn must be visible in at least one timeline,
        # with the resume booked as recompute
        pre = [tl for tl in recent
               if any(e["event"] == "preempted" for e in tl["events"])]
        assert pre, "demo must capture a preempted request's timeline"
        ev = [e for e in pre[0]["events"] if e["event"] == "preempted"][0]
        assert ev["reason"] in ("capacity_growth", "admission")
        assert any(e["event"] == "prefill_chunk" and e.get("recompute")
                   for tl in pre for e in tl["events"])

    def test_engine_state_dump(self):
        st = _load("SERVING_HEALTH.json")["engine_state"]
        assert st["scheduler"]["active"] == 0, "demo drained"
        assert st["kv"]["allocated"] == 0
        assert st["kv"]["usable"] >= 1
        assert st["compile"] == {"decode_signatures": 1,
                                 "prefill_signatures": 1, "retraces": 0}


class TestFleetHealthJson:
    """FLEET_HEALTH.json — the fleet flight-recorder forensics example
    (``python -m deepspeed_tpu.telemetry.fleet --demo``: three
    subprocess-simulated ranks — rank 3 with a slow checkpoint persist —
    plus one real dp=8 virtual-mesh engine as fleet rank 0 carrying an
    injected 20 ms input stall and a perturbed Dense_1 replica). The
    pins enforce the exact integer re-add invariant, the rule firings
    with their attributions, and demo-scale floors so a test-sized
    accidental write can never pass as the committed example (the PR-4
    clobber lesson — tests always snapshot into tmp)."""

    def test_schema(self):
        doc = _load("FLEET_HEALTH.json")
        assert doc["schema"] == "deepspeed_tpu.fleet_health/1"
        for key in ("verdict", "rules", "ranks", "n_ranks", "counters",
                    "desync", "anomalies", "windows"):
            assert key in doc, f"FLEET_HEALTH.json lost key {key!r}"
        assert doc["verdict"] in ("healthy", "watch", "warning",
                                  "critical", "unknown")
        from deepspeed_tpu.telemetry.fleet import RULE_SEVERITY
        for a in doc["anomalies"]:
            assert a["rule"] in RULE_SEVERITY
            assert a["severity"] == RULE_SEVERITY[a["rule"]]

    def test_per_rank_window_sums_re_add_exactly(self):
        """Every duration is an integer µs count; the ring's per-rank
        window values must re-add to the per-rank totals EXACTLY, and
        every ledger-sourced record's categories must partition its wall
        time EXACTLY — any drift is an accounting bug, not noise."""
        doc = _load("FLEET_HEALTH.json")
        assert doc["counters"]["windows_dropped"] == 0
        assert doc["counters"]["windows_in_ring"] == \
            doc["counters"]["windows_judged"]
        for rank, tot in doc["ranks"].items():
            wins = [w["per_rank"][rank] for w in doc["windows"]
                    if rank in w["per_rank"]]
            assert len(wins) == tot["windows"]
            assert tot["wall_us"] == sum(w["wall_us"] for w in wins)
            assert tot["steps"] == sum(w["steps"] for w in wins)
            assert tot["step_time_us"] == sum(
                w["step_time_us"]["sum"] for w in wins)
            assert tot["input_wait_us"] == sum(
                w["input_wait_us"] for w in wins)
            assert tot["checkpoint_save_us"] == sum(
                w["checkpoint_save_us"] for w in wins)
            for c, v in tot["categories_us"].items():
                assert v == sum(w["categories_us"][c] for w in wins)
            for w in wins:
                assert isinstance(w["wall_us"], int)
                if w["categories_us"] is not None:
                    assert sum(w["categories_us"].values()) == \
                        w["wall_us"]

    def test_straggler_attribution(self):
        """The injected 20 ms stall rank must be NAMED, with a badput
        share consistent with the skew and its own ledger's dominant
        category (the stall is input_wait, and the verdict says so)."""
        doc = _load("FLEET_HEALTH.json")
        skews = [a for a in doc["anomalies"]
                 if a["rule"] == "step_time_skew"]
        assert skews, ("the committed example must demonstrate straggler "
                       "attribution — a clean file is a stale regen")
        for a in skews:
            assert a["slow_rank"] == 0, \
                "the demo's stalled rank is fleet rank 0"
            assert 0.5 <= a["badput_share"] <= 0.95
            assert a["slow_rank_dominant_badput"] == "input_wait"
            assert f"rank {a['slow_rank']}" in a["detail"]
        iw = [a for a in doc["anomalies"]
              if a["rule"] == "input_wait_skew"]
        assert iw and all(a["rank"] == 0 for a in iw)
        ck = [a for a in doc["anomalies"]
              if a["rule"] == "checkpoint_persist_skew"]
        assert ck and ck[0]["rank"] == 3
        assert ck[0]["max_us"] >= 200_000

    def test_desync_sentinel_with_provenance(self):
        doc = _load("FLEET_HEALTH.json")
        des = [a for a in doc["anomalies"] if a["rule"] == "desync"]
        assert des, ("the committed example must demonstrate the desync "
                     "sentinel (the demo perturbs a Dense_1 replica)")
        a = des[0]
        assert a["severity"] == "critical"
        assert a["buckets"] == ["Dense_1"]
        assert a["replicas"] == [{"rank": 0, "replica": 3}]
        assert doc["verdict"] == "critical"
        c = doc["counters"]
        # pre-perturbation windows checked CLEAN: the sentinel ran and
        # found nothing, so the firing is a detection, not a default
        assert c["desync_checks"] > c["desync_mismatches"] >= 1
        assert doc["desync"]["mismatch_buckets"] == ["Dense_1"]
        assert "Dense_1" in doc["desync"]["buckets"]

    def test_demo_scale_floors(self):
        doc = _load("FLEET_HEALTH.json")
        # a unit-test-sized accidental write can never pass as the
        # committed example
        assert doc["n_ranks"] >= 4
        assert doc["counters"]["windows_judged"] >= 8
        assert sum(t["steps"] for t in doc["ranks"].values()) >= 48
        assert doc["counters"]["records_loaded"] >= 32
        # the real-engine rank carries a full ledger breakdown with a
        # real XLA compile booked
        r0 = doc["ranks"]["0"]
        assert r0["categories_us"].get("compile", 0) > 0
        assert r0["categories_us"]["input_wait"] > 0
        assert sum(a for a in r0["categories_us"].values()) == \
            r0["wall_us"]

    def test_skew_view_recorded_on_every_window(self):
        """The windows carry the cross-rank extremes whether or not a
        rule fired — the artifact shows the skew trajectory."""
        doc = _load("FLEET_HEALTH.json")
        assert doc["windows"]
        for w in doc["windows"]:
            assert len(w["ranks"]) == doc["n_ranks"]
            st = w["skew"]["step_time"]
            assert st["slow_mean_us"] >= st["fast_mean_us"] > 0
            assert 0.0 <= st["skew_frac"] <= 1.0
        idx = [w["index"] for w in doc["windows"]]
        assert idx == sorted(idx)


class TestTuneReportJson:
    """TUNE_REPORT.json — the goodput-autotuner acceptance proof
    (tests/perf/autotune_bench.py): a real two-stage search whose floors
    are the ISSUE-8 acceptance criteria — >= 1 candidate pruned at
    compile time with reject reason 'hbm', probes scored by the ledger's
    goodput fraction, the winner measurably faster than the base config,
    and at most ONE compile per candidate across the whole run."""

    def test_schema(self):
        doc = _load("TUNE_REPORT.json")
        assert doc["schema"] == "deepspeed_tpu.tune_report/1"
        for key in ("metric", "dp_world", "device", "space",
                    "n_candidates", "stage1", "stage2", "compile",
                    "candidates", "winner"):
            assert key in doc, f"TUNE_REPORT.json lost key {key!r}"
        assert doc["metric"] == "goodput"
        assert doc["n_candidates"] == len(doc["candidates"]) >= 6
        assert doc["dp_world"] >= 8
        dev = doc["device"]
        assert dev["memory_budget_bytes"] > 0
        assert 0.0 < dev["memory_headroom"] <= 1.0

    def test_compile_time_pruning_fired(self):
        doc = _load("TUNE_REPORT.json")
        pruned = [c for c in doc["candidates"] if c["status"] == "pruned"]
        assert pruned, (
            "the committed example must demonstrate compile-time pruning "
            "— a run where nothing was rejected is a stale/defanged regen")
        assert doc["stage1"]["pruned"] == len(pruned)
        limit = doc["device"]["memory_budget_bytes"] * \
            doc["device"]["memory_headroom"]
        for c in pruned:
            assert c["reject_reason"] == "hbm"
            # the rejection is the compiled program's own memory
            # analysis crossing the budget — and the candidate was
            # never executed or probed
            assert c["hbm_watermark_bytes"] > limit
            assert c["probe"] is None

    def test_probes_goodput_scored(self):
        doc = _load("TUNE_REPORT.json")
        probed = [c for c in doc["candidates"] if c["status"] == "probed"]
        assert len(probed) >= 2
        assert doc["stage2"]["probed"] == len(probed)
        for c in probed:
            p = c["probe"]
            assert p["goodput_scored"] is True
            assert 0.0 < p["goodput_fraction"] <= 1.0
            assert p["goodput_step_time_s"] >= p["step_time_s"] > 0
            assert p["score_s_per_sample"] > 0
        # the base config (id 0) is the measured yardstick
        base = doc["candidates"][0]
        assert base["overrides"] == {}
        assert base["status"] == "probed"

    def test_one_compile_per_candidate(self):
        doc = _load("TUNE_REPORT.json")
        comp = doc["compile"]
        assert comp["train_step_compiles"] == comp["candidates_compiled"]
        assert comp["probe_train_step_compiles"] == 0, (
            "a measured probe paid a train-step compile — the stage-1 "
            "artifact adoption regressed")
        for c in doc["candidates"]:
            if c["probe"] is not None:
                assert c["probe"]["artifact_reused"] is True
                assert c["probe"]["aot_fallback_calls"] == 0

    def test_winner_beats_base(self):
        doc = _load("TUNE_REPORT.json")
        w = doc["winner"]
        probed = [c for c in doc["candidates"] if c["probe"]]
        assert w["score_s_per_sample"] == min(
            c["probe"]["score_s_per_sample"] for c in probed)
        assert w["vs_base_speedup"] >= 1.05, (
            "the tuned config no longer beats the base config's goodput-"
            "scored step time — do not commit this regen")
        assert w["config"]["train_micro_batch_size_per_gpu"] == \
            w["overrides"].get(
                "micro_batch",
                w["config"]["train_micro_batch_size_per_gpu"])


class TestNorthstarAotJson:
    def test_schema_and_scale(self):
        doc = _load("NORTHSTAR_AOT.json")
        for key in ("config", "n_params", "per_chip_state_bytes",
                    "collectives", "collectives_detail",
                    "cpu_backend_memory_analysis",
                    "xla_flops_per_chip_per_step"):
            assert key in doc, f"NORTHSTAR_AOT.json lost key {key!r}"
        # the proof is gpt2-xl 1.5B over 16 chips — a smaller regen is a
        # silently downgraded artifact
        assert doc["n_params"] > 1.4e9
        assert doc["config"]["n_devices"] == 16
        assert doc["config"]["zero_stage"] == 3

    def test_analyses_not_zeroed(self):
        doc = _load("NORTHSTAR_AOT.json")
        assert doc["xla_flops_per_chip_per_step"] > 0
        mem = doc["cpu_backend_memory_analysis"]
        assert mem["temp_bytes"] > 0 and mem["argument_bytes"] > 0
        # the string-count-era stale artifact held all-reduce: 7; the real
        # program has hundreds (48 layers x ~12 per-param reductions)
        assert doc["collectives"].get("all-reduce", 0) > 100
        detail = doc["collectives_detail"]
        for key in ("result_bytes", "wire_bytes_per_chip",
                    "bytes_by_mesh_axis", "total_wire_bytes_per_chip"):
            assert key in detail, f"collectives_detail lost {key!r}"
        assert detail["total_wire_bytes_per_chip"] == \
            sum(detail["bytes_by_mesh_axis"].values())
        # every collective kind with a nonzero instruction count must
        # carry wire bytes (a zeroed parse commits 0s here)
        for kind, n in doc["collectives"].items():
            if n and kind in detail["wire_bytes_per_chip"]:
                assert detail["wire_bytes_per_chip"][kind] > 0

    def test_state_bytes_sum(self):
        st = _load("NORTHSTAR_AOT.json")["per_chip_state_bytes"]
        assert st["total"] == st["params"] + st["opt_state"] + \
            st["acc_grads"]


class TestGuardianJson:
    """GUARDIAN.json — the self-healing guardian's action journal demo
    (regenerate: `python -m deepspeed_tpu.runtime.guardian demo --out
    GUARDIAN.json`). The demo drives a real fp16 engine through a
    scripted incident; a regen that silently took no actions (wiring
    rot) or journaled failures must fail here, not ship."""

    def test_schema_and_keys(self):
        doc = _load("GUARDIAN.json")
        from deepspeed_tpu.runtime.guardian import GUARDIAN_SCHEMA
        assert doc["schema"] == GUARDIAN_SCHEMA
        for key in ("armed", "policies", "rules_seen", "sources_seen",
                    "actions", "action_counts", "admission_paused",
                    "last_step"):
            assert key in doc, f"GUARDIAN.json lost key {key!r}"
        assert doc["armed"] is True
        # the journal is self-describing: the armed budgets ride along
        pol = doc["policies"]
        for key in ("emergency_checkpoint", "emergency_rules",
                    "max_emergency_checkpoints", "rollback",
                    "divergence_window", "divergence_streak",
                    "rollback_cooldown_steps", "max_rollbacks",
                    "fp16_rescue", "serving_degrade", "pause_rules",
                    "resume_clear_steps"):
            assert key in pol, f"policies lost key {key!r}"

    def test_demo_floors_two_distinct_actions(self):
        """The scripted incident must produce BOTH training-side
        policies actually firing and succeeding: an emergency
        checkpoint (first grad_norm_spike) and a rollback (confirmed
        divergence)."""
        doc = _load("GUARDIAN.json")
        ok_actions = {a["action"] for a in doc["actions"]
                      if a["outcome"] == "ok"}
        assert {"emergency_checkpoint", "rollback"} <= ok_actions
        # anomaly traffic that drove them
        for rule in ("grad_norm_spike", "loss_spike", "nonfinite_grads"):
            assert doc["rules_seen"].get(rule, 0) >= 1, \
                f"demo regen lost the {rule} firing"
        assert doc["sources_seen"].get("health", 0) >= 3

    def test_action_entries_well_formed(self):
        doc = _load("GUARDIAN.json")
        assert doc["actions"], "demo journal has no actions"
        for a in doc["actions"]:
            for key in ("action", "rule", "step", "unix_time",
                        "outcome", "detail"):
                assert key in a, f"action entry lost key {key!r}"
            assert a["outcome"] == "ok" or \
                a["outcome"].startswith(("skipped:", "failed:")), \
                f"unknown outcome shape {a['outcome']!r}"
        # counts count only performed (ok) actions, exactly
        from collections import Counter
        ok = Counter(a["action"] for a in doc["actions"]
                     if a["outcome"] == "ok")
        assert doc["action_counts"] == dict(ok)

    def test_rollback_targeted_user_tag_not_emergency(self):
        """The poisoned-state hazard pin: the demo writes an emergency
        tag BEFORE the divergence, yet the rollback must restore the
        USER tag — an emergency tag as first-choice target means the
        exclusion logic rotted."""
        from deepspeed_tpu.runtime.guardian import EMERGENCY_TAG_PREFIX
        doc = _load("GUARDIAN.json")
        rolls = [a for a in doc["actions"] if a["action"] == "rollback"]
        assert rolls and rolls[0]["outcome"] == "ok"
        assert rolls[0]["result"].startswith("global_step")
        ems = [a for a in doc["actions"]
               if a["action"] == "emergency_checkpoint"]
        assert ems and ems[0]["result"].startswith(EMERGENCY_TAG_PREFIX)
        # and the emergency save happened before the rollback
        assert ems[0]["step"] < rolls[0]["step"]

    def test_budgets_respected(self):
        doc = _load("GUARDIAN.json")
        pol = doc["policies"]
        counts = doc["action_counts"]
        assert counts.get("emergency_checkpoint", 0) \
            <= pol["max_emergency_checkpoints"]
        assert counts.get("rollback", 0) <= pol["max_rollbacks"]
        assert counts.get("fp16_rescue", 0) <= pol["max_fp16_rescues"]


class TestStepAnatomyJson:
    """STEP_ANATOMY.json — the committed demo-scale step-anatomy report
    (``python -m deepspeed_tpu.telemetry.step_anatomy --demo --out
    STEP_ANATOMY.json``): 3 synthetic steps x 2 device lanes exercising
    every category, module attribution joined to health buckets, and
    flagged measured-vs-predicted drift — exactly the schema
    ``engine.profile_step`` writes from a real capture."""

    def test_schema(self):
        doc = _load("STEP_ANATOMY.json")
        assert doc["schema"] == "deepspeed_tpu.step_anatomy/1"
        for key in ("captured_steps", "device_wall_s", "categories_s",
                    "category_fractions", "steps", "lanes", "top_ops",
                    "module_attribution", "collective_overlap",
                    "measured_vs_predicted", "ops_joined_to_hlo",
                    "ops_total", "source"):
            assert key in doc, f"STEP_ANATOMY.json lost key {key!r}"

    def test_categories_sum_to_device_wall(self):
        from deepspeed_tpu.telemetry.step_anatomy import CATEGORIES
        doc = _load("STEP_ANATOMY.json")
        cats = doc["categories_s"]
        assert set(cats) == set(CATEGORIES)
        wall = doc["device_wall_s"]
        assert wall > 0
        assert abs(sum(cats.values()) - wall) <= 0.01 * wall, (
            f"categories sum {sum(cats.values())} != wall {wall} — the "
            f"coverage-sweep invariant broke")
        # per-step rows re-add to the wall too
        assert abs(sum(s["span_s"] for s in doc["steps"]) - wall) \
            <= 0.01 * wall

    def test_demo_scale_floors(self):
        # a test-sized accidental overwrite can never pass as the example
        doc = _load("STEP_ANATOMY.json")
        assert doc["captured_steps"] >= 3
        assert len(doc["lanes"]) >= 2
        assert doc["ops_total"] >= 5
        assert all(v > 0 for v in doc["categories_s"].values()), (
            "the demo exercises every category; a zero means the "
            "categorizer or the regen regressed")

    def test_attribution_and_drift_demonstrated(self):
        doc = _load("STEP_ANATOMY.json")
        att = doc["module_attribution"]["matmul_convolution"]
        assert att and all(r["module"] for r in att)
        assert any(r["bucket"] for r in att), (
            "module->health-bucket join lost")
        rows = {r["category"] for r in doc["measured_vs_predicted"]}
        assert {"compute", "memory", "comm"} <= rows
        assert any(r["flagged"] for r in doc["measured_vs_predicted"]), (
            "the committed example must demonstrate a flagged drift row")
        ov = doc["collective_overlap"]
        assert ov["collective_s"] > 0
        assert abs(ov["hidden_behind_compute_s"] + ov["exposed_s"]
                   - ov["collective_s"]) <= 1e-9


class TestMemoryAnatomyJson:
    """MEMORY_ANATOMY.json — the committed demo-scale HBM residency
    report (``python -m deepspeed_tpu.telemetry.memory_observatory
    --demo --out MEMORY_ANATOMY.json``): a measured
    ``device_memory_profile`` attributed across the five categories and
    the PR-3 module buckets with EXACT integer sums, plus a nonzero
    measured-vs-predicted watermark drift grounded in the PR-2
    pre-flight — exactly the schema ``engine.memory_report`` writes."""

    def test_schema(self):
        doc = _load("MEMORY_ANATOMY.json")
        assert doc["schema"] == "deepspeed_tpu.memory_anatomy/1"
        for key in ("verdict", "source", "step", "live_total_bytes",
                    "buffer_count", "categories", "buckets", "watermark",
                    "budget", "kv", "rules", "counters", "top_samples",
                    "anomalies", "ring"):
            assert key in doc, f"MEMORY_ANATOMY.json lost key {key!r}"
        assert doc["source"] == "jax.profiler.device_memory_profile"

    def test_categories_and_buckets_readd_exactly(self):
        from deepspeed_tpu.telemetry.memory_observatory import CATEGORIES
        doc = _load("MEMORY_ANATOMY.json")
        cats = doc["categories"]
        assert tuple(cats) == CATEGORIES
        total = doc["live_total_bytes"]
        assert total > 0
        # EXACT integer re-add — the sums-by-construction invariant
        assert sum(c["bytes"] for c in cats.values()) == total, (
            "per-category bytes no longer re-add to the live total")
        for cat in ("params", "optimizer_state"):
            assert sum(doc["buckets"][cat].values()) == \
                cats[cat]["bytes"], (
                    f"{cat} bucket bytes no longer re-add to the "
                    f"category total")

    def test_drift_demonstrated_against_preflight(self):
        doc = _load("MEMORY_ANATOMY.json")
        wm = doc["watermark"]
        assert wm["prediction_source"] == "cost_explorer.preflight", (
            "the example must measure drift against the PR-2 pre-flight")
        assert wm["predicted_bytes"] > 0
        assert wm["measured_peak_bytes"] > 0
        assert wm["drift"] is not None and wm["drift"] != 0, (
            "the committed example must demonstrate a nonzero "
            "measured-vs-predicted drift")
        detail = wm["prediction_detail"]
        assert detail["hbm_watermark_bytes_per_device"] > 0
        assert detail["n_devices"] >= 1
        assert wm["predicted_bytes"] == \
            detail["hbm_watermark_bytes_per_device"] * detail["n_devices"]

    def test_demo_scale_floors(self):
        # a test-sized accidental overwrite can never pass as the example
        doc = _load("MEMORY_ANATOMY.json")
        assert doc["categories"]["params"]["bytes"] > 0
        assert doc["categories"]["optimizer_state"]["bytes"] > 0
        assert doc["buffer_count"] > 10
        assert doc["counters"]["windows_seen"] >= 4
        assert len(doc["buckets"]["params"]) >= 2, (
            "the demo model has multiple layers; fewer than 2 param "
            "buckets means the PR-3 spec join regressed")
        assert doc["top_samples"], "forensics top-samples lost"
        # the CPU demo has no device bytes_limit: the refusal of the
        # host-RSS fallback (satellite 1) must be on the record
        assert doc["budget"]["host_budget_refused"] is True


class TestChronicleJson:
    """CHRONICLE.json — the committed run-chronicle demo timeline
    (``python -m deepspeed_tpu.telemetry.chronicle --demo``: a real fp16
    engine driven through the guardian's scripted divergence incident
    with every emitter armed). The pins enforce the ordering invariant
    that makes cross-stream causality claims valid at all, plus
    demo-scale emitter coverage — a regen where a monitor stopped
    emitting shows up as a missing kind, not a silently thinner file."""

    def test_schema(self):
        doc = _load("CHRONICLE.json")
        assert doc["schema"] == "deepspeed_tpu.chronicle/1"
        for key in ("job_name", "rank", "n_events", "dropped", "events",
                    "counts_by_kind", "counts_by_source", "first_t_us",
                    "last_t_us"):
            assert key in doc, f"CHRONICLE.json lost key {key!r}"
        assert doc["dropped"] == 0, "the demo must not hit the event cap"
        assert doc["n_events"] == len(doc["events"]) >= 15

    def test_events_strictly_ordered_on_the_us_axis(self):
        """The chronicle's one contract: every consumer (the correlator,
        a human reading the render) may assume strict (t_us, seq) order.
        Integer µs — floats would let two events tie un-orderably."""
        ev = _load("CHRONICLE.json")["events"]
        keys = []
        for e in ev:
            assert isinstance(e["t_us"], int) and isinstance(e["seq"], int)
            assert isinstance(e["unix_us"], int)
            keys.append((e["t_us"], e["seq"]))
        assert keys == sorted(keys) and len(set(keys)) == len(keys), (
            "events are not strictly (t_us, seq)-ordered — the "
            "monotone-clock invariant broke")
        doc = _load("CHRONICLE.json")
        assert doc["first_t_us"] == ev[0]["t_us"]
        assert doc["last_t_us"] == ev[-1]["t_us"]

    def test_every_emitter_family_present(self):
        doc = _load("CHRONICLE.json")
        counts = doc["counts_by_kind"]
        # the demo exercises engine lifecycle, the ledger's window
        # ticks, the chaos harness, the health monitor and the guardian
        for kind in ("lifecycle", "goodput_window", "chaos", "anomaly",
                     "action"):
            assert counts.get(kind, 0) >= 1, (
                f"no {kind!r} events — that emitter's wiring rotted")
        assert sum(counts.values()) == doc["n_events"]
        by_src = doc["counts_by_source"]
        for src in ("engine", "goodput", "chaos", "health", "guardian"):
            assert by_src.get(src, 0) >= 1
        assert sum(by_src.values()) == doc["n_events"]

    def test_lifecycle_brackets_the_run(self):
        ev = _load("CHRONICLE.json")["events"]
        phases = [e["phase"] for e in ev if e["kind"] == "lifecycle"]
        # init first, close last, and the incident's save/load pair
        assert phases[0] == "init"
        assert phases[-1] == "close"
        assert {"first_compile", "checkpoint_save",
                "checkpoint_load"} <= set(phases)
        assert ev[0]["kind"] == "lifecycle" and ev[0]["phase"] == "init"
        assert ev[-1]["kind"] == "lifecycle" and ev[-1]["phase"] == "close"

    def test_guardian_actions_carry_the_join_key(self):
        ev = _load("CHRONICLE.json")["events"]
        actions = [e for e in ev if e["kind"] == "action"]
        assert actions, "the guardian demo must take actions"
        for a in actions:
            assert a["source"] == "guardian"
            assert a["rule"], "action events must carry the rule edge"
            assert a["action"]
        assert any(a["action"] == "rollback" for a in actions)


class TestIncidentsJson:
    """INCIDENTS.json — the incident correlator's verdict over the
    committed CHRONICLE.json. The acceptance scenario itself: the chaos
    poison -> nonfinite cascade -> guardian rollback must collapse into
    exactly ONE incident whose ranked root cause names the injection,
    with a goodput cost that re-adds against the ledger windows riding
    the same timeline."""

    def test_schema(self):
        doc = _load("INCIDENTS.json")
        assert doc["schema"] == "deepspeed_tpu.incidents/1"
        for key in ("job_name", "n_events", "params", "incidents"):
            assert key in doc, f"INCIDENTS.json lost key {key!r}"
        assert doc["params"]["step_window"] >= 1
        assert doc["params"]["time_window_us"] >= 1
        # correlated over the SAME committed timeline
        assert doc["n_events"] == _load("CHRONICLE.json")["n_events"]

    def test_one_incident_rooted_at_the_poison(self):
        doc = _load("INCIDENTS.json")
        assert len(doc["incidents"]) == 1, (
            "the chaos cascade must correlate into exactly ONE incident "
            "— fragmentation means the join keys rotted")
        inc = doc["incidents"][0]
        rc = inc["root_cause"]
        assert rc["kind"] == "chaos" and rc["chaos"] == "divergence"
        assert isinstance(rc["step"], int)
        assert rc["why"], "the ranking must say WHY this event is root"
        # the root cause is the chronicle's own chaos event, verbatim
        chaos = [e for e in _load("CHRONICLE.json")["events"]
                 if e["kind"] == "chaos"]
        assert len(chaos) == 1
        assert (rc["seq"], rc["t_us"], rc["step"]) == \
            (chaos[0]["seq"], chaos[0]["t_us"], chaos[0]["step"])

    def test_chain_well_formed(self):
        inc = _load("INCIDENTS.json")["incidents"][0]
        assert inc["severity"] == "critical"
        assert "nonfinite_grads" in inc["rules"]
        assert "rollback" in inc["actions"]
        assert inc["start_t_us"] <= inc["end_t_us"]
        assert inc["duration_us"] == inc["end_t_us"] - inc["start_t_us"]
        assert inc["start_step"] <= inc["end_step"]
        seqs = [e["seq"] for e in inc["events"]]
        assert seqs == sorted(seqs) and len(inc["events"]) >= 4
        assert inc["artifacts"], "member anomalies carry their snapshots"

    def test_goodput_cost_readds_exactly(self):
        inc = _load("INCIDENTS.json")["incidents"][0]
        cost = inc["goodput_cost"]
        assert cost["window_indices"], (
            "no ledger windows overlapped the incident — the demo's "
            "goodput cadence or the overlap join rotted")
        assert all(isinstance(v, int)
                   for v in cost["badput_us"].values())
        assert "device_compute" not in cost["badput_us"], \
            "goodput categories must not be booked as badput"
        assert cost["badput_total_us"] == \
            sum(cost["badput_us"].values()) > 0


class TestSloReportJson:
    """SLO_REPORT.json — the SLO burn-rate monitor's demo capture
    (``python -m deepspeed_tpu.telemetry.slo --demo``): a serving_ttft
    objective observed healthy until the slow window is provably clean,
    then an injected TTFT degradation burns BOTH windows, the page-tier
    anomaly reaches a live guardian (admission pause), and the incident
    correlator joins the chain naming the objective."""

    def test_schema(self):
        doc = _load("SLO_REPORT.json")
        assert doc["schema"] == "deepspeed_tpu.slo/1"
        assert doc["enabled"] is True
        assert doc["clock"] == "monotonic_us"
        for key in ("job_name", "params", "evals", "objectives",
                    "rule_counts", "anomalies", "demo", "incidents"):
            assert key in doc, f"SLO_REPORT.json lost key {key!r}"
        p = doc["params"]
        assert 0 < p["fast_window_s"] < p["slow_window_s"]
        assert p["burn_threshold"] > 0 and 0 < p["min_span_frac"] <= 1

    def test_objective_paged_on_both_windows(self):
        doc = _load("SLO_REPORT.json")
        obj = doc["objectives"]["serving_ttft"]
        assert obj["kind"] == "latency"
        assert obj["metric"] == "serving_ttft_ms"
        assert 0 < obj["target"] < 1
        assert abs(obj["error_budget"] - (1 - obj["target"])) < 1e-9
        assert obj["tier"] == "page" and obj["active"] is True
        for wname in ("fast", "slow"):
            w = obj["windows"][wname]
            assert w["eligible"] and w["burning"], (
                f"the demo must burn the {wname} window — a page "
                f"requires BOTH")
            assert w["burn"] >= doc["params"]["burn_threshold"]
        assert obj["pages"] >= 1

    def test_windows_readd_on_the_us_axis(self):
        """Samples are cumulative (t_us, bad, total) tuples; a window's
        span must re-add exactly against its anchor/newest stamps —
        the goodput ring's integer-axis discipline."""
        obj = _load("SLO_REPORT.json")["objectives"]["serving_ttft"]
        for wname in ("fast", "slow"):
            w = obj["windows"][wname]
            assert w["span_us"] == w["t_newest_us"] - w["t_anchor_us"]
            assert w["window_us"] == int(w["window_s"] * 1e6)
            assert w["span_us"] >= 0 and w["samples"] >= 2
            assert w["delta_total"] >= w["delta_bad"] >= 0
        tot = obj["totals"]
        assert tot["total"] >= tot["bad"] > 0

    def test_demo_floors(self):
        """A zeroed/stale regen cannot look like this: real healthy
        traffic first (the slow window must be spanned clean), then a
        real degradation with enough observations to burn."""
        doc = _load("SLO_REPORT.json")
        demo = doc["demo"]
        assert demo["healthy_evals"] >= 10
        assert demo["degraded_evals"] >= 1
        assert demo["observations"] >= 40
        assert doc["evals"] == (demo["healthy_evals"]
                                + demo["degraded_evals"])
        assert doc["rule_counts"].get("slo_burn_page", 0) >= 1
        anoms = [a for a in doc["anomalies"]
                 if a["rule"] == "slo_burn_page"]
        assert anoms, "the page anomaly must ride the report history"
        a = anoms[0]
        assert a["severity"] == "critical"
        assert a["objective"] == "serving_ttft"
        assert "serving_ttft" in a["detail"], (
            "the page's detail must NAME the objective — that is what "
            "the chronicle/incident chain carries to the pager")
        assert a["burn_fast"] >= 1 and a["burn_slow"] >= 1

    def test_guardian_closed_the_loop(self):
        demo = _load("SLO_REPORT.json")["demo"]
        assert demo["admission_paused"] is True
        assert "slo_burn_page" in demo["guardian_received"]
        assert demo["pause_rules_fired"] == ["slo_burn_page"]
        acts = [x for x in demo["guardian_actions"]
                if x["action"] == "serving_pause"]
        assert acts and acts[0]["rule"] == "slo_burn_page"
        assert acts[0]["outcome"] == "ok"

    def test_incident_names_the_objective(self):
        doc = _load("SLO_REPORT.json")
        inc_doc = doc["incidents"]
        assert inc_doc["schema"] == "deepspeed_tpu.incidents/1"
        incs = [i for i in inc_doc["incidents"]
                if "slo_burn_page" in i["rules"]]
        assert len(incs) == 1, (
            "the burn -> pause chain must correlate into exactly ONE "
            "incident carrying the slo_burn_page rule")
        inc = incs[0]
        assert inc["severity"] == "critical"
        assert "serving_pause" in inc["actions"]
        rc = inc["root_cause"]
        assert rc["kind"] == "anomaly" and rc["source"] == "slo"
        assert rc["rule"] == "slo_burn_page"
        assert "serving_ttft" in rc.get("detail", ""), (
            "the root cause must name WHICH objective burned")


class TestFleetControlJson:
    """FLEET_CONTROL.json — the fleet-federation demo snapshot
    (``python -m deepspeed_tpu.telemetry.federation --demo --steps
    120``): 3 subprocess ranks on one run dir, one rank degraded
    (bad_frac 0.6) and one chaos-SIGKILL'd mid-run. The pins are the
    demo FLOORS a zeroed/stale regen cannot fake: a real multi-rank
    scrape, a really-stale victim, a fleet-scope page with per-rank
    attribution, and a cross-rank incident rooted at the injected
    fault's rank and step."""

    def test_schema_and_fleet_shape(self):
        doc = _load("FLEET_CONTROL.json")
        assert doc["schema"] == "deepspeed_tpu.fleet_control/1"
        assert doc["enabled"] is True
        assert doc["job_name"] == "federation_demo"
        peers = doc["peers"]
        assert doc["n_peers"] == len(peers) >= 3
        by_status = {}
        for p in peers:
            by_status.setdefault(p["status"], []).append(p)
            assert {"goodput", "slo"} <= set(p["providers"]), (
                f"rank {p['rank']} was scraped without its reports")
            assert p["scrapes"] >= 1 and p["events_held"] >= 1
            assert p["cursor"] >= 0
        # the SIGKILL'd rank is REALLY stale; the others really scraped
        assert doc["n_stale"] == len(by_status.get("stale", [])) >= 1
        assert len(by_status.get("ok", [])) >= 2
        victim = by_status["stale"][0]
        assert victim["errors"] >= 1, (
            "a killed peer must accumulate scrape errors, not just "
            "age out")

    def test_counters_reconcile_with_the_peers(self):
        doc = _load("FLEET_CONTROL.json")
        c = doc["counters"]
        peers = doc["peers"]
        assert c["scrapes_total"] == sum(p["scrapes"] for p in peers)
        # every merged event is held by some peer ring (the demo never
        # overflows the 4096 ring), and the report agrees with itself
        assert c["events_merged_total"] == \
            sum(p["events_held"] for p in peers)
        assert doc["n_merged_events"] == c["events_merged_total"] >= 100
        assert c["scrape_errors_total"] >= 1   # the killed peer

    def test_fleet_slo_paged_with_rank_attribution(self):
        doc = _load("FLEET_CONTROL.json")
        slo = doc["slo"]
        assert slo["schema"] == "deepspeed_tpu.slo/1"
        assert set(slo["objectives"]) == {"fleet_goodput", "fleet_ttft"}
        assert slo["rule_counts"].get("slo_burn_page", 0) >= 1
        pages = [a for a in slo["anomalies"]
                 if a["rule"] == "slo_burn_page"
                 and a["objective"] == "fleet_goodput"]
        assert pages, "the degraded rank must page the fleet objective"
        page = pages[0]
        assert page["severity"] == "critical"
        # per-rank attribution: the burn names WHO is dominant (the
        # demo degrades rank 1) and carries the per-rank deltas
        assert page["dominant_rank"] == 1
        assert "dominant rank 1" in page["detail"]
        deltas = page["rank_bad_deltas"]
        assert len(deltas) >= 2
        assert deltas["1"] >= max(v for k, v in deltas.items()
                                  if k != "1")

    def test_cross_rank_incident_roots_at_the_fault(self):
        doc = _load("FLEET_CONTROL.json")
        inc_doc = doc["incidents"]
        assert inc_doc["schema"] == "deepspeed_tpu.incidents/1"
        incs = [i for i in inc_doc["incidents"]
                if i["root_cause"].get("chaos") == "sigkill"]
        assert incs, (
            "the injected SIGKILL must correlate into a chaos-rooted "
            "cross-rank incident")
        inc = incs[0]
        rc = inc["root_cause"]
        # the root cause NAMES the fault's rank and step — what a
        # merged timeline buys over per-rank ones
        fault_rank = rc["rank"]
        assert isinstance(fault_rank, int) and rc["kind"] == "chaos"
        assert isinstance(rc["step"], int) and rc["step"] >= 1
        assert inc["severity"] == "critical"
        assert "step_time_skew" in inc["rules"], (
            "the OTHER ranks' skew anomalies must join the chain")
        member_ranks = {e.get("rank") for e in inc["events"]}
        assert fault_rank in member_ranks
        assert len(member_ranks) >= 3, (
            f"a cross-rank incident must span the fleet, got "
            f"{sorted(member_ranks)}")

    def test_events_tail_is_strictly_ordered_and_rank_stamped(self):
        doc = _load("FLEET_CONTROL.json")
        tail = doc["events_tail"]
        assert len(tail) >= 100
        assert all("rank" in e for e in tail)
        assert {e["rank"] for e in tail} >= {0, 1}
        keys = [(e["t_us"], e["seq"], e["rank"]) for e in tail]
        assert keys == sorted(keys) and len(set(keys)) == len(keys), (
            "the merged fleet timeline must be strictly "
            "(t_us, seq, rank)-ordered")


class TestRepoRootArtifactLint:
    """Every committed repo-root ``*.json`` must (a) strict-parse — no
    bare NaN/Infinity tokens, which only Python's json accepts — (b)
    carry a ``deepspeed_tpu.*`` schema tag where its family defines one,
    and (c) be claimed by a pin class in this file. A new artifact that
    lands without a pin fails HERE, so coverage can't silently decay."""

    # families that predate the schema tag (pinned structurally above)
    NO_SCHEMA = {"BASELINE.json", "EXPLAIN.json", "NORTHSTAR_AOT.json"}

    # every name some test pins — here, or for BASELINE.json the
    # model-parity tests (tests/model)
    COVERED = {"EXPLAIN.json", "HEALTH.json", "GOODPUT.json",
               "SERVING_HEALTH.json", "FLEET_HEALTH.json",
               "TUNE_REPORT.json", "GUARDIAN.json", "STEP_ANATOMY.json",
               "NORTHSTAR_AOT.json", "OVERLAP_BENCH.json",
               "PREFETCH_BENCH.json", "CKPT_BENCH.json",
               "SERVING_BENCH.json", "MEMORY_ANATOMY.json",
               "CHRONICLE.json", "INCIDENTS.json", "SLO_REPORT.json",
               "FLEET_CONTROL.json", "BASELINE.json"}

    def _root_artifacts(self):
        import glob
        names = sorted(os.path.basename(p)
                       for p in glob.glob(os.path.join(ROOT, "*.json")))
        assert names, "repo root lost its committed artifacts"
        return names

    def test_all_root_json_strict_parse(self):
        for name in self._root_artifacts():
            doc = _load(name)   # _load fails the test on bare NaN/Inf
            assert isinstance(doc, dict), (
                f"{name} is not a JSON object at top level")

    def test_schema_tag_where_the_family_defines_one(self):
        for name in self._root_artifacts():
            if name in self.NO_SCHEMA:
                continue
            doc = _load(name)
            schema = doc.get("schema")
            assert isinstance(schema, str) and \
                schema.startswith("deepspeed_tpu."), (
                    f"{name} carries no deepspeed_tpu.* schema tag — "
                    f"tag it (and pin it here), or add it to NO_SCHEMA "
                    f"if its family predates tagging")
            assert "/" in schema, f"{name} schema has no version suffix"

    def test_every_artifact_is_pinned(self):
        for name in self._root_artifacts():
            assert name in self.COVERED, (
                    f"{name} is committed at the repo root but no test "
                    f"in test_artifacts.py pins it — add a pin class "
                    f"and register it in COVERED")
