"""DeepSpeed-schema JSON config system.

Parity with ``deepspeed/runtime/config.py`` (``DeepSpeedConfig`` at
config.py:789, accessors :77-680): the same JSON file a DeepSpeed user
writes is accepted unchanged. The reference exposes ~200 flat ``get_*``
helpers feeding engine properties; here the parsed values land on typed
attributes with identical names so ``engine.train_batch_size()`` etc. keep
working.

Batch-size triangulation follows the reference exactly:
``train_batch_size = micro_batch_per_gpu * gradient_accumulation_steps *
data_parallel_world_size`` — any two determine the third; one alone pins
the others to 1/world; all three must agree.
"""

import json
import os

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.utils.logging import logger

# Optimizer names (reference: runtime/config.py:77-96)
ADAM_OPTIMIZER = "adam"
ADAMW_OPTIMIZER = "adamw"
LAMB_OPTIMIZER = "lamb"
ONEBIT_ADAM_OPTIMIZER = "onebitadam"
ONEBIT_LAMB_OPTIMIZER = "onebitlamb"
SGD_OPTIMIZER = "sgd"
ADAGRAD_OPTIMIZER = "adagrad"
DEEPSPEED_OPTIMIZERS = [
    ADAM_OPTIMIZER, ADAMW_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
    ONEBIT_LAMB_OPTIMIZER, SGD_OPTIMIZER, ADAGRAD_OPTIMIZER
]


class DeepSpeedConfigError(Exception):
    pass


def get_scalar_param(d, name, default):
    return d.get(name, default)


class DeepSpeedConfigObject:
    """repr-able plain config holder."""

    def repr(self):
        return self.__dict__

    def __repr__(self):
        return json.dumps(self.__dict__, sort_keys=True, indent=4, default=repr)


class DeepSpeedFP16Config(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        fp16 = param_dict.get(C.FP16, {}) or {}
        self.enabled = fp16.get(C.FP16_ENABLED, C.FP16_ENABLED_DEFAULT)
        self.loss_scale = fp16.get(C.FP16_LOSS_SCALE, C.FP16_LOSS_SCALE_DEFAULT)
        self.initial_scale_power = fp16.get(C.FP16_INITIAL_SCALE_POWER,
                                            C.FP16_INITIAL_SCALE_POWER_DEFAULT)
        self.loss_scale_window = fp16.get(C.FP16_LOSS_SCALE_WINDOW,
                                          C.FP16_LOSS_SCALE_WINDOW_DEFAULT)
        self.hysteresis = fp16.get(C.FP16_HYSTERESIS, C.FP16_HYSTERESIS_DEFAULT)
        self.min_loss_scale = fp16.get(C.FP16_MIN_LOSS_SCALE,
                                       C.FP16_MIN_LOSS_SCALE_DEFAULT)
        self.master_weights_and_grads = fp16.get(
            C.FP16_MASTER_WEIGHTS_AND_GRADS, C.FP16_MASTER_WEIGHTS_AND_GRADS_DEFAULT)

    @property
    def dynamic_loss_scale(self):
        return self.loss_scale == 0


class DeepSpeedBF16Config(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        bf = param_dict.get(C.BFLOAT16, param_dict.get(C.BFLOAT16_OLD, {})) or {}
        self.enabled = bf.get(C.BFLOAT16_ENABLED, C.BFLOAT16_ENABLED_DEFAULT)


class DeepSpeedTensorboardConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        tb = param_dict.get(C.TENSORBOARD, {}) or {}
        self.enabled = tb.get(C.TENSORBOARD_ENABLED, C.TENSORBOARD_ENABLED_DEFAULT)
        self.output_path = tb.get(C.TENSORBOARD_OUTPUT_PATH,
                                  C.TENSORBOARD_OUTPUT_PATH_DEFAULT)
        self.job_name = tb.get(C.TENSORBOARD_JOB_NAME, C.TENSORBOARD_JOB_NAME_DEFAULT)


class DeepSpeedTelemetryConfig(DeepSpeedConfigObject):
    """``telemetry`` block (TPU-native, beyond the reference schema):
    structured spans + compile watch + metrics sinks (telemetry/).

    Env overrides (sweep ergonomics, applied after JSON): ``DS_TELEMETRY``
    = 1/0 force-toggles ``enabled``; ``DS_TELEMETRY_DIR`` overrides
    ``output_path``; ``DS_COST_EXPLORER`` / ``DS_TELEMETRY_HEALTH`` /
    ``DS_TELEMETRY_GOODPUT`` / ``DS_TELEMETRY_MEMORY`` /
    ``DS_TELEMETRY_CHRONICLE`` / ``DS_TELEMETRY_SERVER`` /
    ``DS_TELEMETRY_SLO`` = 1/0 force-toggle the cost-explorer / health /
    goodput / memory / chronicle / obs-server / slo sub-blocks."""

    def __init__(self, param_dict):
        t = param_dict.get(C.TELEMETRY, {}) or {}
        self.enabled = t.get(C.TELEMETRY_ENABLED, C.TELEMETRY_ENABLED_DEFAULT)
        self.output_path = t.get(C.TELEMETRY_OUTPUT_PATH,
                                 C.TELEMETRY_OUTPUT_PATH_DEFAULT)
        self.job_name = t.get(C.TELEMETRY_JOB_NAME,
                              C.TELEMETRY_JOB_NAME_DEFAULT)
        self.trace = t.get(C.TELEMETRY_TRACE, C.TELEMETRY_TRACE_DEFAULT)
        self.compile_watch = t.get(C.TELEMETRY_COMPILE_WATCH,
                                   C.TELEMETRY_COMPILE_WATCH_DEFAULT)
        self.jsonl = t.get(C.TELEMETRY_JSONL, C.TELEMETRY_JSONL_DEFAULT)
        self.prometheus = t.get(C.TELEMETRY_PROMETHEUS,
                                C.TELEMETRY_PROMETHEUS_DEFAULT)
        self.memory_metrics = t.get(C.TELEMETRY_MEMORY_METRICS,
                                    C.TELEMETRY_MEMORY_METRICS_DEFAULT)
        self.max_trace_events = t.get(C.TELEMETRY_MAX_TRACE_EVENTS,
                                      C.TELEMETRY_MAX_TRACE_EVENTS_DEFAULT)
        # cost_explorer sub-block (telemetry/cost_explorer.py): compiled-
        # program census + roofline/MFU + HBM pre-flight. Flattened onto
        # cost_explorer_* attributes; 0 peaks mean "detect from the chip".
        ce = t.get(C.COST_EXPLORER, {}) or {}
        self.cost_explorer_enabled = ce.get(C.COST_EXPLORER_ENABLED,
                                            C.COST_EXPLORER_ENABLED_DEFAULT)
        self.cost_explorer_peak_tflops = ce.get(
            C.COST_EXPLORER_PEAK_TFLOPS, C.COST_EXPLORER_PEAK_TFLOPS_DEFAULT)
        self.cost_explorer_peak_hbm_gbps = ce.get(
            C.COST_EXPLORER_PEAK_HBM_GBPS,
            C.COST_EXPLORER_PEAK_HBM_GBPS_DEFAULT)
        self.cost_explorer_ici_gbps = ce.get(
            C.COST_EXPLORER_ICI_GBPS, C.COST_EXPLORER_ICI_GBPS_DEFAULT)
        self.cost_explorer_hbm_gb = ce.get(C.COST_EXPLORER_HBM_GB,
                                           C.COST_EXPLORER_HBM_GB_DEFAULT)
        self.cost_explorer_preflight = ce.get(
            C.COST_EXPLORER_PREFLIGHT, C.COST_EXPLORER_PREFLIGHT_DEFAULT)
        self.cost_explorer_preflight_threshold = ce.get(
            C.COST_EXPLORER_PREFLIGHT_THRESHOLD,
            C.COST_EXPLORER_PREFLIGHT_THRESHOLD_DEFAULT)
        # health sub-block (telemetry/health.py): in-step numerics stats +
        # host-side anomaly rules + HEALTH.json forensics. Flattened onto
        # health_* attributes like the cost explorer.
        h = t.get(C.TELEMETRY_HEALTH, {}) or {}
        self.health_enabled = h.get(C.HEALTH_ENABLED,
                                    C.HEALTH_ENABLED_DEFAULT)
        self.health_bucket_depth = h.get(C.HEALTH_BUCKET_DEPTH,
                                         C.HEALTH_BUCKET_DEPTH_DEFAULT)
        self.health_cadence = h.get(C.HEALTH_CADENCE,
                                    C.HEALTH_CADENCE_DEFAULT)
        self.health_ewma_alpha = h.get(C.HEALTH_EWMA_ALPHA,
                                       C.HEALTH_EWMA_ALPHA_DEFAULT)
        self.health_loss_spike_zscore = h.get(
            C.HEALTH_LOSS_SPIKE_ZSCORE, C.HEALTH_LOSS_SPIKE_ZSCORE_DEFAULT)
        self.health_grad_spike_zscore = h.get(
            C.HEALTH_GRAD_SPIKE_ZSCORE, C.HEALTH_GRAD_SPIKE_ZSCORE_DEFAULT)
        self.health_warmup_samples = h.get(C.HEALTH_WARMUP_SAMPLES,
                                           C.HEALTH_WARMUP_SAMPLES_DEFAULT)
        self.health_overflow_streak = h.get(
            C.HEALTH_OVERFLOW_STREAK, C.HEALTH_OVERFLOW_STREAK_DEFAULT)
        self.health_stall_window = h.get(C.HEALTH_STALL_WINDOW,
                                         C.HEALTH_STALL_WINDOW_DEFAULT)
        self.health_stall_rel_delta = h.get(
            C.HEALTH_STALL_REL_DELTA, C.HEALTH_STALL_REL_DELTA_DEFAULT)
        self.health_ring_size = h.get(C.HEALTH_RING_SIZE,
                                      C.HEALTH_RING_SIZE_DEFAULT)
        self.health_snapshot_file = h.get(C.HEALTH_SNAPSHOT_FILE,
                                          C.HEALTH_SNAPSHOT_FILE_DEFAULT)
        self.health_trace_on_anomaly = h.get(
            C.HEALTH_TRACE_ON_ANOMALY, C.HEALTH_TRACE_ON_ANOMALY_DEFAULT)
        # goodput sub-block (telemetry/ledger.py): wall-clock goodput/
        # badput attribution + GOODPUT.json forensics + on-anomaly
        # profiler capture. Flattened onto goodput_* attributes.
        g = t.get(C.TELEMETRY_GOODPUT, {}) or {}
        self.goodput_enabled = g.get(C.GOODPUT_ENABLED,
                                     C.GOODPUT_ENABLED_DEFAULT)
        self.goodput_cadence = g.get(C.GOODPUT_CADENCE,
                                     C.GOODPUT_CADENCE_DEFAULT)
        self.goodput_input_wait_frac = g.get(
            C.GOODPUT_INPUT_WAIT_FRAC, C.GOODPUT_INPUT_WAIT_FRAC_DEFAULT)
        self.goodput_unattributed_frac = g.get(
            C.GOODPUT_UNATTRIBUTED_FRAC,
            C.GOODPUT_UNATTRIBUTED_FRAC_DEFAULT)
        self.goodput_warmup_windows = g.get(
            C.GOODPUT_WARMUP_WINDOWS, C.GOODPUT_WARMUP_WINDOWS_DEFAULT)
        self.goodput_window_ring = g.get(C.GOODPUT_WINDOW_RING,
                                         C.GOODPUT_WINDOW_RING_DEFAULT)
        self.goodput_snapshot_file = g.get(C.GOODPUT_SNAPSHOT_FILE,
                                           C.GOODPUT_SNAPSHOT_FILE_DEFAULT)
        self.goodput_profiler_capture = g.get(
            C.GOODPUT_PROFILER_CAPTURE, C.GOODPUT_PROFILER_CAPTURE_DEFAULT)
        self.goodput_profiler_capture_steps = g.get(
            C.GOODPUT_PROFILER_CAPTURE_STEPS,
            C.GOODPUT_PROFILER_CAPTURE_STEPS_DEFAULT)
        self.goodput_profiler_max_captures = g.get(
            C.GOODPUT_PROFILER_MAX_CAPTURES,
            C.GOODPUT_PROFILER_MAX_CAPTURES_DEFAULT)
        self.goodput_profiler_dir = g.get(C.GOODPUT_PROFILER_DIR,
                                          C.GOODPUT_PROFILER_DIR_DEFAULT)
        # anatomy sub-block (telemetry/step_anatomy.py): measured device-
        # time attribution from bounded jax.profiler captures. Flattened
        # onto anatomy_* attributes.
        an = t.get(C.TELEMETRY_ANATOMY, {}) or {}
        self.anatomy_enabled = an.get(C.ANATOMY_ENABLED,
                                      C.ANATOMY_ENABLED_DEFAULT)
        self.anatomy_capture_steps = int(an.get(
            C.ANATOMY_CAPTURE_STEPS, C.ANATOMY_CAPTURE_STEPS_DEFAULT))
        self.anatomy_keep_raw_traces = int(an.get(
            C.ANATOMY_KEEP_RAW_TRACES, C.ANATOMY_KEEP_RAW_TRACES_DEFAULT))
        self.anatomy_report_file = an.get(C.ANATOMY_REPORT_FILE,
                                          C.ANATOMY_REPORT_FILE_DEFAULT)
        # fleet sub-block (telemetry/fleet.py): cross-rank flight recorder
        # — per-rank window-record shipping + rank-0 skew/desync
        # sentinels. Flattened onto fleet_* attributes.
        fl = t.get(C.TELEMETRY_FLEET, {}) or {}
        self.fleet_enabled = fl.get(C.FLEET_ENABLED,
                                    C.FLEET_ENABLED_DEFAULT)
        self.fleet_run_dir = fl.get(C.FLEET_RUN_DIR,
                                    C.FLEET_RUN_DIR_DEFAULT)
        self.fleet_rank = int(fl.get(C.FLEET_RANK, C.FLEET_RANK_DEFAULT))
        self.fleet_cadence = int(fl.get(C.FLEET_CADENCE,
                                        C.FLEET_CADENCE_DEFAULT))
        self.fleet_desync = fl.get(C.FLEET_DESYNC, C.FLEET_DESYNC_DEFAULT)
        self.fleet_desync_cadence = int(fl.get(
            C.FLEET_DESYNC_CADENCE, C.FLEET_DESYNC_CADENCE_DEFAULT))
        self.fleet_step_time_skew_frac = float(fl.get(
            C.FLEET_STEP_TIME_SKEW_FRAC,
            C.FLEET_STEP_TIME_SKEW_FRAC_DEFAULT))
        self.fleet_input_wait_skew_frac = float(fl.get(
            C.FLEET_INPUT_WAIT_SKEW_FRAC,
            C.FLEET_INPUT_WAIT_SKEW_FRAC_DEFAULT))
        self.fleet_checkpoint_skew_frac = float(fl.get(
            C.FLEET_CHECKPOINT_SKEW_FRAC,
            C.FLEET_CHECKPOINT_SKEW_FRAC_DEFAULT))
        self.fleet_checkpoint_skew_floor_ms = float(fl.get(
            C.FLEET_CHECKPOINT_SKEW_FLOOR_MS,
            C.FLEET_CHECKPOINT_SKEW_FLOOR_MS_DEFAULT))
        self.fleet_warmup_windows = int(fl.get(
            C.FLEET_WARMUP_WINDOWS, C.FLEET_WARMUP_WINDOWS_DEFAULT))
        self.fleet_window_ring = int(fl.get(C.FLEET_WINDOW_RING,
                                            C.FLEET_WINDOW_RING_DEFAULT))
        self.fleet_snapshot_file = fl.get(C.FLEET_SNAPSHOT_FILE,
                                          C.FLEET_SNAPSHOT_FILE_DEFAULT)
        self.fleet_background_ship = fl.get(
            C.FLEET_BACKGROUND_SHIP, C.FLEET_BACKGROUND_SHIP_DEFAULT)
        # memory sub-block (telemetry/memory_observatory.py): HBM residency
        # observatory — measured buffer attribution + leak/drift/frag/oom
        # sentinels. Flattened onto memory_* attributes.
        m = t.get(C.TELEMETRY_MEMORY, {}) or {}
        self.memory_enabled = m.get(C.MEMORY_ENABLED,
                                    C.MEMORY_ENABLED_DEFAULT)
        self.memory_cadence = int(m.get(C.MEMORY_CADENCE,
                                        C.MEMORY_CADENCE_DEFAULT))
        self.memory_snapshot_file = m.get(C.MEMORY_SNAPSHOT_FILE,
                                          C.MEMORY_SNAPSHOT_FILE_DEFAULT)
        self.memory_report_file = m.get(C.MEMORY_REPORT_FILE,
                                        C.MEMORY_REPORT_FILE_DEFAULT)
        self.memory_leak_windows = int(m.get(
            C.MEMORY_LEAK_WINDOWS, C.MEMORY_LEAK_WINDOWS_DEFAULT))
        self.memory_warmup_windows = int(m.get(
            C.MEMORY_WARMUP_WINDOWS, C.MEMORY_WARMUP_WINDOWS_DEFAULT))
        self.memory_drift_threshold = float(m.get(
            C.MEMORY_DRIFT_THRESHOLD, C.MEMORY_DRIFT_THRESHOLD_DEFAULT))
        self.memory_frag_threshold = float(m.get(
            C.MEMORY_FRAG_THRESHOLD, C.MEMORY_FRAG_THRESHOLD_DEFAULT))
        self.memory_headroom = float(m.get(C.MEMORY_HEADROOM,
                                           C.MEMORY_HEADROOM_DEFAULT))
        self.memory_budget_bytes = int(m.get(
            C.MEMORY_BUDGET_BYTES, C.MEMORY_BUDGET_BYTES_DEFAULT))
        self.memory_ring_size = int(m.get(C.MEMORY_RING_SIZE,
                                          C.MEMORY_RING_SIZE_DEFAULT))
        # chronicle sub-block (telemetry/chronicle.py + incidents.py):
        # the run-wide causal event timeline. Flattened onto chronicle_*.
        ch = t.get(C.TELEMETRY_CHRONICLE, {}) or {}
        self.chronicle_enabled = ch.get(C.CHRONICLE_ENABLED,
                                        C.CHRONICLE_ENABLED_DEFAULT)
        self.chronicle_run_dir = ch.get(C.CHRONICLE_RUN_DIR,
                                        C.CHRONICLE_RUN_DIR_DEFAULT)
        self.chronicle_max_events = int(ch.get(
            C.CHRONICLE_MAX_EVENTS, C.CHRONICLE_MAX_EVENTS_DEFAULT))
        self.chronicle_summary_file = ch.get(
            C.CHRONICLE_SUMMARY_FILE, C.CHRONICLE_SUMMARY_FILE_DEFAULT)
        self.chronicle_incidents_file = ch.get(
            C.CHRONICLE_INCIDENTS_FILE, C.CHRONICLE_INCIDENTS_FILE_DEFAULT)
        self.chronicle_step_window = int(ch.get(
            C.CHRONICLE_STEP_WINDOW, C.CHRONICLE_STEP_WINDOW_DEFAULT))
        self.chronicle_time_window_s = float(ch.get(
            C.CHRONICLE_TIME_WINDOW_S, C.CHRONICLE_TIME_WINDOW_S_DEFAULT))
        self.chronicle_background = ch.get(C.CHRONICLE_BACKGROUND,
                                           C.CHRONICLE_BACKGROUND_DEFAULT)
        # server sub-block (telemetry/obs_server.py): the live HTTP
        # scrape/status endpoint. Flattened onto server_*.
        sv = t.get(C.TELEMETRY_SERVER, {}) or {}
        self.server_enabled = sv.get(C.SERVER_ENABLED,
                                     C.SERVER_ENABLED_DEFAULT)
        self.server_host = sv.get(C.SERVER_HOST, C.SERVER_HOST_DEFAULT)
        self.server_port = int(sv.get(C.SERVER_PORT,
                                      C.SERVER_PORT_DEFAULT))
        self.server_token = sv.get(C.SERVER_TOKEN,
                                   C.SERVER_TOKEN_DEFAULT)
        self.server_events_tail = int(sv.get(
            C.SERVER_EVENTS_TAIL, C.SERVER_EVENTS_TAIL_DEFAULT))
        # slo sub-block (telemetry/slo.py): multi-window burn-rate
        # alerting over declarative objectives. Flattened onto slo_*.
        sl = t.get(C.TELEMETRY_SLO, {}) or {}
        self.slo_enabled = sl.get(C.SLO_ENABLED, C.SLO_ENABLED_DEFAULT)
        self.slo_fast_window_s = float(sl.get(
            C.SLO_FAST_WINDOW_S, C.SLO_FAST_WINDOW_S_DEFAULT))
        self.slo_slow_window_s = float(sl.get(
            C.SLO_SLOW_WINDOW_S, C.SLO_SLOW_WINDOW_S_DEFAULT))
        self.slo_burn_threshold = float(sl.get(
            C.SLO_BURN_THRESHOLD, C.SLO_BURN_THRESHOLD_DEFAULT))
        self.slo_eval_interval_s = float(sl.get(
            C.SLO_EVAL_INTERVAL_S, C.SLO_EVAL_INTERVAL_S_DEFAULT))
        self.slo_objectives = tuple(sl.get(C.SLO_OBJECTIVES)
                                    or C.SLO_OBJECTIVES_DEFAULT)
        self.slo_goodput_target = float(sl.get(
            C.SLO_GOODPUT_TARGET, C.SLO_GOODPUT_TARGET_DEFAULT))
        self.slo_ttft_target = float(sl.get(
            C.SLO_TTFT_TARGET, C.SLO_TTFT_TARGET_DEFAULT))
        self.slo_ttft_threshold_ms = float(sl.get(
            C.SLO_TTFT_THRESHOLD_MS, C.SLO_TTFT_THRESHOLD_MS_DEFAULT))
        self.slo_e2e_target = float(sl.get(
            C.SLO_E2E_TARGET, C.SLO_E2E_TARGET_DEFAULT))
        self.slo_e2e_threshold_ms = float(sl.get(
            C.SLO_E2E_THRESHOLD_MS, C.SLO_E2E_THRESHOLD_MS_DEFAULT))
        self.slo_snapshot_file = sl.get(C.SLO_SNAPSHOT_FILE,
                                        C.SLO_SNAPSHOT_FILE_DEFAULT)
        # federation sub-block (telemetry/federation.py): cross-process
        # mission control — peer-scraping aggregator, merged fleet
        # timeline, fleet-level SLO burn. Flattened onto federation_*.
        fed = t.get(C.TELEMETRY_FEDERATION, {}) or {}
        self.federation_enabled = fed.get(C.FEDERATION_ENABLED,
                                          C.FEDERATION_ENABLED_DEFAULT)
        self.federation_peers = tuple(fed.get(C.FEDERATION_PEERS)
                                      or C.FEDERATION_PEERS_DEFAULT)
        self.federation_run_dir = fed.get(C.FEDERATION_RUN_DIR,
                                          C.FEDERATION_RUN_DIR_DEFAULT)
        self.federation_aggregator = str(fed.get(
            C.FEDERATION_AGGREGATOR, C.FEDERATION_AGGREGATOR_DEFAULT))
        self.federation_scrape_interval_s = float(fed.get(
            C.FEDERATION_SCRAPE_INTERVAL_S,
            C.FEDERATION_SCRAPE_INTERVAL_S_DEFAULT))
        self.federation_timeout_s = float(fed.get(
            C.FEDERATION_TIMEOUT_S, C.FEDERATION_TIMEOUT_S_DEFAULT))
        self.federation_stale_after_s = float(fed.get(
            C.FEDERATION_STALE_AFTER_S,
            C.FEDERATION_STALE_AFTER_S_DEFAULT))
        self.federation_events_ring = int(fed.get(
            C.FEDERATION_EVENTS_RING, C.FEDERATION_EVENTS_RING_DEFAULT))
        self.federation_snapshot_file = fed.get(
            C.FEDERATION_SNAPSHOT_FILE, C.FEDERATION_SNAPSHOT_FILE_DEFAULT)
        self.federation_goodput_target = float(fed.get(
            C.FEDERATION_GOODPUT_TARGET,
            C.FEDERATION_GOODPUT_TARGET_DEFAULT))
        self.federation_ttft_target = float(fed.get(
            C.FEDERATION_TTFT_TARGET, C.FEDERATION_TTFT_TARGET_DEFAULT))
        env = os.environ.get("DS_TELEMETRY")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        env_dir = os.environ.get("DS_TELEMETRY_DIR")
        if env_dir:
            self.output_path = env_dir
        env_ce = os.environ.get("DS_COST_EXPLORER")
        if env_ce is not None:
            self.cost_explorer_enabled = env_ce.lower() in (
                "1", "true", "yes", "on")
        env_h = os.environ.get("DS_TELEMETRY_HEALTH")
        if env_h is not None:
            self.health_enabled = env_h.lower() in ("1", "true", "yes", "on")
        env_g = os.environ.get("DS_TELEMETRY_GOODPUT")
        if env_g is not None:
            self.goodput_enabled = env_g.lower() in ("1", "true", "yes",
                                                     "on")
        env_an = os.environ.get("DS_TELEMETRY_ANATOMY")
        if env_an is not None:
            self.anatomy_enabled = env_an.lower() in ("1", "true", "yes",
                                                      "on")
        env_f = os.environ.get("DS_TELEMETRY_FLEET")
        if env_f is not None:
            self.fleet_enabled = env_f.lower() in ("1", "true", "yes",
                                                   "on")
        env_fd = os.environ.get("DS_TELEMETRY_FLEET_RUN_DIR")
        if env_fd:
            self.fleet_run_dir = env_fd
        env_fr = os.environ.get("DS_TELEMETRY_FLEET_RANK")
        if env_fr is not None:
            self.fleet_rank = int(env_fr)
        env_m = os.environ.get("DS_TELEMETRY_MEMORY")
        if env_m is not None:
            self.memory_enabled = env_m.lower() in ("1", "true", "yes",
                                                    "on")
        env_ch = os.environ.get("DS_TELEMETRY_CHRONICLE")
        if env_ch is not None:
            self.chronicle_enabled = env_ch.lower() in ("1", "true",
                                                        "yes", "on")
        env_sv = os.environ.get("DS_TELEMETRY_SERVER")
        if env_sv is not None:
            self.server_enabled = env_sv.lower() in ("1", "true", "yes",
                                                     "on")
        env_sl = os.environ.get("DS_TELEMETRY_SLO")
        if env_sl is not None:
            self.slo_enabled = env_sl.lower() in ("1", "true", "yes",
                                                  "on")
        env_fe = os.environ.get("DS_TELEMETRY_FEDERATION")
        if env_fe is not None:
            self.federation_enabled = env_fe.lower() in ("1", "true",
                                                         "yes", "on")
        env_frd = os.environ.get("DS_TELEMETRY_FEDERATION_RUN_DIR")
        if env_frd:
            self.federation_run_dir = env_frd
        env_fp = os.environ.get("DS_TELEMETRY_FEDERATION_PEERS")
        if env_fp:
            self.federation_peers = tuple(
                p.strip() for p in env_fp.split(",") if p.strip())
        env_fa = os.environ.get("DS_TELEMETRY_FEDERATION_AGGREGATOR")
        if env_fa:
            self.federation_aggregator = env_fa
        if self.anatomy_capture_steps < 1:
            raise DeepSpeedConfigError(
                f"telemetry.anatomy.capture_steps must be >= 1, got "
                f"{self.anatomy_capture_steps}")
        if self.anatomy_keep_raw_traces < 0:
            raise DeepSpeedConfigError(
                f"telemetry.anatomy.keep_raw_traces must be >= 0, got "
                f"{self.anatomy_keep_raw_traces}")
        if self.fleet_cadence < 0:
            raise DeepSpeedConfigError(
                f"telemetry.fleet.cadence must be >= 0, got "
                f"{self.fleet_cadence}")
        if self.fleet_desync_cadence < 0:
            raise DeepSpeedConfigError(
                f"telemetry.fleet.desync_cadence must be >= 0, got "
                f"{self.fleet_desync_cadence}")
        for name, frac in (("step_time_skew_frac",
                            self.fleet_step_time_skew_frac),
                           ("input_wait_skew_frac",
                            self.fleet_input_wait_skew_frac),
                           ("checkpoint_skew_frac",
                            self.fleet_checkpoint_skew_frac)):
            if not 0.0 < frac <= 1.0:
                raise DeepSpeedConfigError(
                    f"telemetry.fleet.{name} must be in (0, 1], got "
                    f"{frac}")
        if self.fleet_window_ring < 1:
            raise DeepSpeedConfigError(
                f"telemetry.fleet.window_ring must be >= 1, got "
                f"{self.fleet_window_ring}")
        if self.memory_cadence < 0:
            raise DeepSpeedConfigError(
                f"telemetry.memory.cadence must be >= 0, got "
                f"{self.memory_cadence}")
        if self.memory_leak_windows < 2:
            raise DeepSpeedConfigError(
                f"telemetry.memory.leak_windows must be >= 2, got "
                f"{self.memory_leak_windows}")
        if self.memory_warmup_windows < 0:
            raise DeepSpeedConfigError(
                f"telemetry.memory.warmup_windows must be >= 0, got "
                f"{self.memory_warmup_windows}")
        if not 0.0 < self.memory_drift_threshold:
            raise DeepSpeedConfigError(
                f"telemetry.memory.drift_threshold must be > 0, got "
                f"{self.memory_drift_threshold}")
        if not 0.0 < self.memory_frag_threshold <= 1.0:
            raise DeepSpeedConfigError(
                f"telemetry.memory.frag_threshold must be in (0, 1], got "
                f"{self.memory_frag_threshold}")
        if not 0.0 < self.memory_headroom <= 1.0:
            raise DeepSpeedConfigError(
                f"telemetry.memory.headroom must be in (0, 1], got "
                f"{self.memory_headroom}")
        if self.memory_budget_bytes < 0:
            raise DeepSpeedConfigError(
                f"telemetry.memory.budget_bytes must be >= 0, got "
                f"{self.memory_budget_bytes}")
        if self.memory_ring_size < 1:
            raise DeepSpeedConfigError(
                f"telemetry.memory.ring_size must be >= 1, got "
                f"{self.memory_ring_size}")
        if self.chronicle_max_events < 1:
            raise DeepSpeedConfigError(
                f"telemetry.chronicle.max_events must be >= 1, got "
                f"{self.chronicle_max_events}")
        if self.chronicle_step_window < 0:
            raise DeepSpeedConfigError(
                f"telemetry.chronicle.step_window must be >= 0, got "
                f"{self.chronicle_step_window}")
        if self.chronicle_time_window_s <= 0:
            raise DeepSpeedConfigError(
                f"telemetry.chronicle.time_window_s must be > 0, got "
                f"{self.chronicle_time_window_s}")
        if not 0 <= self.server_port <= 65535:
            raise DeepSpeedConfigError(
                f"telemetry.server.port must be in [0, 65535], got "
                f"{self.server_port}")
        if self.server_events_tail < 1:
            raise DeepSpeedConfigError(
                f"telemetry.server.events_tail must be >= 1, got "
                f"{self.server_events_tail}")
        if not 0.0 < self.slo_fast_window_s < self.slo_slow_window_s:
            raise DeepSpeedConfigError(
                f"telemetry.slo windows must satisfy 0 < fast_window_s "
                f"< slow_window_s, got {self.slo_fast_window_s} / "
                f"{self.slo_slow_window_s}")
        if self.slo_burn_threshold <= 0:
            raise DeepSpeedConfigError(
                f"telemetry.slo.burn_threshold must be > 0, got "
                f"{self.slo_burn_threshold}")
        if self.slo_eval_interval_s <= 0:
            raise DeepSpeedConfigError(
                f"telemetry.slo.eval_interval_s must be > 0, got "
                f"{self.slo_eval_interval_s}")
        for tname, target in (("goodput_target", self.slo_goodput_target),
                              ("ttft_target", self.slo_ttft_target),
                              ("e2e_target", self.slo_e2e_target)):
            if not 0.0 < target < 1.0:
                raise DeepSpeedConfigError(
                    f"telemetry.slo.{tname} must be in (0, 1), got "
                    f"{target}")
        for mname, ms in (("ttft_threshold_ms",
                           self.slo_ttft_threshold_ms),
                          ("e2e_threshold_ms",
                           self.slo_e2e_threshold_ms)):
            if ms <= 0:
                raise DeepSpeedConfigError(
                    f"telemetry.slo.{mname} must be > 0, got {ms}")
        for o in self.slo_objectives:
            # declarative objectives fail at config time, not first tick
            from deepspeed_tpu.telemetry.slo import normalize_objective
            try:
                normalize_objective(o)
            except ValueError as e:
                raise DeepSpeedConfigError(
                    f"telemetry.slo.objectives: {e}")
        if self.federation_aggregator not in ("auto", "always", "never"):
            raise DeepSpeedConfigError(
                f"telemetry.federation.aggregator must be one of "
                f"auto/always/never, got {self.federation_aggregator!r}")
        for fname, fval in (
                ("scrape_interval_s", self.federation_scrape_interval_s),
                ("timeout_s", self.federation_timeout_s),
                ("stale_after_s", self.federation_stale_after_s)):
            if fval <= 0:
                raise DeepSpeedConfigError(
                    f"telemetry.federation.{fname} must be > 0, got "
                    f"{fval}")
        if self.federation_events_ring < 16:
            raise DeepSpeedConfigError(
                f"telemetry.federation.events_ring must be >= 16, got "
                f"{self.federation_events_ring}")
        for tname, target in (
                ("goodput_target", self.federation_goodput_target),
                ("ttft_target", self.federation_ttft_target)):
            if not 0.0 < target < 1.0:
                raise DeepSpeedConfigError(
                    f"telemetry.federation.{tname} must be in (0, 1), "
                    f"got {target}")
        for p in self.federation_peers:
            if not isinstance(p, str) or not p.startswith("http"):
                raise DeepSpeedConfigError(
                    f"telemetry.federation.peers entries must be http "
                    f"base urls, got {p!r}")


class DeepSpeedDataPrefetchConfig(DeepSpeedConfigObject):
    """``data_prefetch`` block (runtime/prefetch.py): bounded background
    input pipeline — host-stage collate workers + (single-process) device
    double-buffering that overlaps the H2D copy with device compute.

    Env override (sweep ergonomics): ``DS_DATA_PREFETCH`` = 1/0
    force-toggles ``enabled`` after JSON parsing."""

    def __init__(self, param_dict):
        p = param_dict.get(C.DATA_PREFETCH, {}) or {}
        self.enabled = p.get(C.DATA_PREFETCH_ENABLED,
                             C.DATA_PREFETCH_ENABLED_DEFAULT)
        self.depth = int(p.get(C.DATA_PREFETCH_DEPTH,
                               C.DATA_PREFETCH_DEPTH_DEFAULT))
        self.to_device = p.get(C.DATA_PREFETCH_TO_DEVICE,
                               C.DATA_PREFETCH_TO_DEVICE_DEFAULT)
        env = os.environ.get("DS_DATA_PREFETCH")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        if self.depth < 1:
            raise DeepSpeedConfigError(
                f"data_prefetch.depth must be >= 1, got {self.depth}")


class DeepSpeedCommOverlapConfig(DeepSpeedConfigObject):
    """``comm_overlap`` block (runtime/comm_overlap.py): bucketed
    gradient-collective overlap — the train step reduces gradients with
    one psum per size-targeted bucket (issued as the backward produces
    each bucket's grads) instead of one GSPMD all-reduce per grad leaf
    at the step tail. The engine falls back (warn once) outside the
    supported envelope: dp > 1, zero stage <= 1, mp/ep/pp == 1, dense
    grads, default batch sharding.

    Env override (sweep ergonomics): ``DS_COMM_OVERLAP`` = 1/0
    force-toggles ``enabled`` after JSON parsing."""

    def __init__(self, param_dict):
        o = param_dict.get(C.COMM_OVERLAP, {}) or {}
        self.enabled = o.get(C.COMM_OVERLAP_ENABLED,
                             C.COMM_OVERLAP_ENABLED_DEFAULT)
        self.bucket_mb = float(o.get(C.COMM_OVERLAP_BUCKET_MB,
                                     C.COMM_OVERLAP_BUCKET_MB_DEFAULT))
        self.scheduler_flags = o.get(C.COMM_OVERLAP_SCHEDULER_FLAGS,
                                     C.COMM_OVERLAP_SCHEDULER_FLAGS_DEFAULT)
        env = os.environ.get("DS_COMM_OVERLAP")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        if self.bucket_mb <= 0:
            raise DeepSpeedConfigError(
                f"comm_overlap.bucket_mb must be > 0, got {self.bucket_mb}")

    @property
    def bucket_bytes(self):
        return int(self.bucket_mb * (1 << 20))


class DeepSpeedGuardianConfig(DeepSpeedConfigObject):
    """``guardian`` block (runtime/guardian.py): the self-healing
    anomaly->action policy engine. Subscribes to the telemetry monitors'
    ``on_anomaly`` hooks and maps fired rules to bounded, rate-limited
    actions — emergency checkpoint, rollback-to-last-intact, fp16
    loss-scale rescue, serving admission pause/resume. Every action is
    journaled to ``GUARDIAN.json``.

    Env overrides (sweep ergonomics, after JSON parsing):
    ``DS_GUARDIAN`` = 1/0 force-toggles ``enabled``;
    ``DS_GUARDIAN_JOURNAL`` overrides ``journal_file``;
    ``DS_GUARDIAN_MAX_ROLLBACKS`` and ``DS_GUARDIAN_COOLDOWN_STEPS``
    override the rollback budget and the per-action cooldown."""

    def __init__(self, param_dict):
        g = param_dict.get(C.GUARDIAN, {}) or {}
        self.enabled = g.get(C.GUARDIAN_ENABLED, C.GUARDIAN_ENABLED_DEFAULT)
        self.journal_file = g.get(C.GUARDIAN_JOURNAL_FILE,
                                  C.GUARDIAN_JOURNAL_FILE_DEFAULT)
        self.action_cooldown_steps = int(g.get(
            C.GUARDIAN_ACTION_COOLDOWN, C.GUARDIAN_ACTION_COOLDOWN_DEFAULT))
        self.emergency_checkpoint = g.get(
            C.GUARDIAN_EMERGENCY_CHECKPOINT,
            C.GUARDIAN_EMERGENCY_CHECKPOINT_DEFAULT)
        # [] / absent -> the guardian's built-in warning-tier rule set
        from deepspeed_tpu.runtime.guardian import (DEFAULT_EMERGENCY_RULES,
                                                    DEFAULT_PAUSE_RULES)
        self.emergency_rules = tuple(
            g.get(C.GUARDIAN_EMERGENCY_RULES) or DEFAULT_EMERGENCY_RULES)
        self.max_emergency_checkpoints = int(g.get(
            C.GUARDIAN_MAX_EMERGENCY_CHECKPOINTS,
            C.GUARDIAN_MAX_EMERGENCY_CHECKPOINTS_DEFAULT))
        self.rollback = g.get(C.GUARDIAN_ROLLBACK,
                              C.GUARDIAN_ROLLBACK_DEFAULT)
        self.divergence_window = int(g.get(
            C.GUARDIAN_DIVERGENCE_WINDOW,
            C.GUARDIAN_DIVERGENCE_WINDOW_DEFAULT))
        self.divergence_streak = int(g.get(
            C.GUARDIAN_DIVERGENCE_STREAK,
            C.GUARDIAN_DIVERGENCE_STREAK_DEFAULT))
        self.rollback_cooldown_steps = int(g.get(
            C.GUARDIAN_ROLLBACK_COOLDOWN,
            C.GUARDIAN_ROLLBACK_COOLDOWN_DEFAULT))
        self.max_rollbacks = int(g.get(C.GUARDIAN_MAX_ROLLBACKS,
                                       C.GUARDIAN_MAX_ROLLBACKS_DEFAULT))
        self.fp16_rescue = g.get(C.GUARDIAN_FP16_RESCUE,
                                 C.GUARDIAN_FP16_RESCUE_DEFAULT)
        self.max_fp16_rescues = int(g.get(
            C.GUARDIAN_MAX_FP16_RESCUES,
            C.GUARDIAN_MAX_FP16_RESCUES_DEFAULT))
        self.serving_degrade = g.get(C.GUARDIAN_SERVING_DEGRADE,
                                     C.GUARDIAN_SERVING_DEGRADE_DEFAULT)
        self.pause_rules = tuple(
            g.get(C.GUARDIAN_PAUSE_RULES) or DEFAULT_PAUSE_RULES)
        self.resume_clear_steps = int(g.get(
            C.GUARDIAN_RESUME_CLEAR_STEPS,
            C.GUARDIAN_RESUME_CLEAR_STEPS_DEFAULT))
        env = os.environ.get("DS_GUARDIAN")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        env_j = os.environ.get("DS_GUARDIAN_JOURNAL")
        if env_j is not None:
            self.journal_file = env_j
        env_r = os.environ.get("DS_GUARDIAN_MAX_ROLLBACKS")
        if env_r is not None:
            self.max_rollbacks = int(env_r)
        env_c = os.environ.get("DS_GUARDIAN_COOLDOWN_STEPS")
        if env_c is not None:
            self.action_cooldown_steps = int(env_c)
        if self.action_cooldown_steps < 0:
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_ACTION_COOLDOWN} must be >= 0, got "
                f"{self.action_cooldown_steps}")
        if self.divergence_streak < 1:
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_DIVERGENCE_STREAK} must be >= 1, "
                f"got {self.divergence_streak}")
        if self.divergence_window < 1:
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_DIVERGENCE_WINDOW} must be >= 1, "
                f"got {self.divergence_window}")
        if self.max_rollbacks < 0:
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_MAX_ROLLBACKS} must be >= 0, got "
                f"{self.max_rollbacks}")
        if self.rollback_cooldown_steps < 1:
            # a 0 cooldown would let two consecutive divergent steps
            # rollback-loop against the same intact tag
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_ROLLBACK_COOLDOWN} must be >= 1, "
                f"got {self.rollback_cooldown_steps}")
        if self.resume_clear_steps < 1:
            raise DeepSpeedConfigError(
                f"guardian.{C.GUARDIAN_RESUME_CLEAR_STEPS} must be >= 1, "
                f"got {self.resume_clear_steps}")


class DeepSpeedServingObservabilityConfig(DeepSpeedConfigObject):
    """``serving.observability`` sub-block
    (telemetry/serving_observatory.py): per-request lifecycle timelines
    + per-slot Chrome-trace lanes, the slot-step attribution ledger
    (decode_useful/cached_prefill/prefill/recompute/frozen/idle, sums to
    ``steps x max_batch x decode_steps`` by construction), and windowed
    SLO rules escalating warn-once -> throttled ``SERVING_HEALTH.json``
    -> trace flush.

    Env override (sweep ergonomics): ``DS_SERVING_OBS`` = 1/0
    force-toggles ``enabled`` after JSON parsing."""

    def __init__(self, serving_dict):
        o = serving_dict.get(C.SERVING_OBSERVABILITY, {}) or {}
        self.enabled = o.get(C.SERVING_OBS_ENABLED,
                             C.SERVING_OBS_ENABLED_DEFAULT)
        self.window = int(o.get(C.SERVING_OBS_WINDOW,
                                C.SERVING_OBS_WINDOW_DEFAULT))
        self.warmup_windows = int(o.get(C.SERVING_OBS_WARMUP,
                                        C.SERVING_OBS_WARMUP_DEFAULT))
        self.ttft_slo_ms = float(o.get(C.SERVING_OBS_TTFT_SLO_MS,
                                       C.SERVING_OBS_TTFT_SLO_MS_DEFAULT))
        self.ttft_breach_frac = float(
            o.get(C.SERVING_OBS_TTFT_BREACH_FRAC,
                  C.SERVING_OBS_TTFT_BREACH_FRAC_DEFAULT))
        self.queue_growth_windows = int(
            o.get(C.SERVING_OBS_QUEUE_GROWTH_WINDOWS,
                  C.SERVING_OBS_QUEUE_GROWTH_WINDOWS_DEFAULT))
        self.preemption_thrash = int(
            o.get(C.SERVING_OBS_PREEMPTION_THRASH,
                  C.SERVING_OBS_PREEMPTION_THRASH_DEFAULT))
        self.no_progress_steps = int(
            o.get(C.SERVING_OBS_NO_PROGRESS_STEPS,
                  C.SERVING_OBS_NO_PROGRESS_STEPS_DEFAULT))
        self.timeline_ring = int(o.get(C.SERVING_OBS_TIMELINE_RING,
                                       C.SERVING_OBS_TIMELINE_RING_DEFAULT))
        self.window_ring = int(o.get(C.SERVING_OBS_WINDOW_RING,
                                     C.SERVING_OBS_WINDOW_RING_DEFAULT))
        self.trace_lanes = o.get(C.SERVING_OBS_TRACE_LANES,
                                 C.SERVING_OBS_TRACE_LANES_DEFAULT)
        self.snapshot_file = o.get(C.SERVING_OBS_SNAPSHOT_FILE,
                                   C.SERVING_OBS_SNAPSHOT_FILE_DEFAULT)
        env = os.environ.get("DS_SERVING_OBS")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        if self.window < 1:
            raise DeepSpeedConfigError(
                f"serving.observability.window must be >= 1, got "
                f"{self.window}")
        if self.warmup_windows < 0:
            raise DeepSpeedConfigError(
                f"serving.observability.warmup_windows must be >= 0, got "
                f"{self.warmup_windows}")
        if not 0.0 < self.ttft_breach_frac <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.observability.ttft_breach_frac must be in "
                f"(0, 1], got {self.ttft_breach_frac}")
        if self.no_progress_steps < 1:
            raise DeepSpeedConfigError(
                f"serving.observability.no_progress_steps must be >= 1, "
                f"got {self.no_progress_steps}")
        if self.queue_growth_windows < 1:
            raise DeepSpeedConfigError(
                f"serving.observability.queue_growth_windows must be "
                f">= 1, got {self.queue_growth_windows}")
        if self.preemption_thrash < 1:
            # the rule is `window preemptions >= threshold`, and every
            # window has >= 0 preemptions — a 0 threshold would fire the
            # thrash rule on every post-warmup window forever
            raise DeepSpeedConfigError(
                f"serving.observability.preemption_thrash must be >= 1 "
                f"(disable rules with enabled=false), got "
                f"{self.preemption_thrash}")
        if self.ttft_slo_ms <= 0:
            raise DeepSpeedConfigError(
                f"serving.observability.ttft_slo_ms must be > 0, got "
                f"{self.ttft_slo_ms}")


class DeepSpeedServingPrefixCacheConfig(DeepSpeedConfigObject):
    """``serving.prefix_cache`` sub-block (serving/kv_cache.py
    ``PrefixCache``): content-addressed LRU index of FULL KV blocks,
    mapped read-only at admission with copy-on-write forks on divergent
    writes. ``capacity_blocks`` 0 leaves the index uncapped (it is still
    bounded by the block pool — every resident entry holds exactly one
    allocator reference, and refcount-1 entries are reclaimed before any
    preemption fires).

    Env override (sweep ergonomics): ``DS_SERVING_PREFIX_CACHE`` = 1/0
    force-toggles ``enabled``."""

    def __init__(self, serving_dict):
        p = serving_dict.get(C.SERVING_PREFIX_CACHE, {}) or {}
        self.enabled = p.get(C.SERVING_PREFIX_ENABLED,
                             C.SERVING_PREFIX_ENABLED_DEFAULT)
        self.capacity_blocks = int(
            p.get(C.SERVING_PREFIX_CAPACITY_BLOCKS,
                  C.SERVING_PREFIX_CAPACITY_BLOCKS_DEFAULT))
        env = os.environ.get("DS_SERVING_PREFIX_CACHE")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        if self.capacity_blocks < 0:
            raise DeepSpeedConfigError(
                f"serving.prefix_cache.capacity_blocks must be >= 0 "
                f"(0 = uncapped), got {self.capacity_blocks}")


class DeepSpeedServingSpeculativeConfig(DeepSpeedConfigObject):
    """``serving.speculative`` sub-block (serving/speculative.py):
    draft/verify speculative decoding over the paged KV. The default
    draft is the truncated-layer self-draft — ``draft_layers`` 0 picks
    ``n_layer // 4`` (floor 1) at engine construction; ``draft_model``
    null means self-draft (an explicit small model is handed to the
    engine programmatically as ``draft_params``). ``acceptance``
    "exact" keeps outputs bit-exact vs the non-speculative engine;
    "typical" trades parity on sampled slots for acceptance.
    ``acceptance_floor`` arms the observatory's ``speculation_waste``
    rule.

    Env override (sweep ergonomics): ``DS_SERVING_SPEC`` = 1/0
    force-toggles ``enabled``."""

    def __init__(self, serving_dict):
        sp = serving_dict.get(C.SERVING_SPECULATIVE, {}) or {}
        self.enabled = sp.get(C.SERVING_SPEC_ENABLED,
                              C.SERVING_SPEC_ENABLED_DEFAULT)
        self.k = int(sp.get(C.SERVING_SPEC_K, C.SERVING_SPEC_K_DEFAULT))
        self.draft_layers = int(sp.get(C.SERVING_SPEC_DRAFT_LAYERS,
                                       C.SERVING_SPEC_DRAFT_LAYERS_DEFAULT))
        self.draft_model = sp.get(C.SERVING_SPEC_DRAFT_MODEL,
                                  C.SERVING_SPEC_DRAFT_MODEL_DEFAULT)
        self.acceptance = sp.get(C.SERVING_SPEC_ACCEPTANCE,
                                 C.SERVING_SPEC_ACCEPTANCE_DEFAULT)
        self.typical_threshold = float(
            sp.get(C.SERVING_SPEC_TYPICAL_THRESHOLD,
                   C.SERVING_SPEC_TYPICAL_THRESHOLD_DEFAULT))
        self.acceptance_floor = float(
            sp.get(C.SERVING_SPEC_ACCEPTANCE_FLOOR,
                   C.SERVING_SPEC_ACCEPTANCE_FLOOR_DEFAULT))
        env = os.environ.get("DS_SERVING_SPEC")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        if self.k < 1:
            raise DeepSpeedConfigError(
                f"serving.speculative.k must be >= 1, got {self.k}")
        if self.draft_layers < 0:
            raise DeepSpeedConfigError(
                f"serving.speculative.draft_layers must be >= 0 "
                f"(0 = auto), got {self.draft_layers}")
        if self.acceptance not in ("exact", "typical"):
            raise DeepSpeedConfigError(
                f"serving.speculative.acceptance must be 'exact' or "
                f"'typical', got {self.acceptance!r}")
        if not 0.0 < self.typical_threshold <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.speculative.typical_threshold must be in "
                f"(0, 1], got {self.typical_threshold}")
        if not 0.0 <= self.acceptance_floor <= 1.0:
            raise DeepSpeedConfigError(
                f"serving.speculative.acceptance_floor must be in "
                f"[0, 1], got {self.acceptance_floor}")
        if self.draft_model is not None and not isinstance(
                self.draft_model, str):
            raise DeepSpeedConfigError(
                f"serving.speculative.draft_model must be null "
                f"(self-draft) or a string tag, got "
                f"{type(self.draft_model).__name__}")


class DeepSpeedServingRouterConfig(DeepSpeedConfigObject):
    """``serving.router`` sub-block (serving/router.py
    ``ServingRouter``): admission scoring weights over per-replica
    signals (queue depth, KV occupancy, recent SLO breaches) plus
    prefix-affinity. ``breach_penalty`` dominates the load terms by
    design — a breaching replica only receives work when every replica
    is breaching (failover, not permanent blacklist)."""

    def __init__(self, serving_dict):
        r = serving_dict.get(C.SERVING_ROUTER, {}) or {}
        self.replicas = int(r.get(C.SERVING_ROUTER_REPLICAS,
                                  C.SERVING_ROUTER_REPLICAS_DEFAULT))
        self.affinity_weight = float(
            r.get(C.SERVING_ROUTER_AFFINITY_WEIGHT,
                  C.SERVING_ROUTER_AFFINITY_WEIGHT_DEFAULT))
        self.queue_weight = float(
            r.get(C.SERVING_ROUTER_QUEUE_WEIGHT,
                  C.SERVING_ROUTER_QUEUE_WEIGHT_DEFAULT))
        self.occupancy_weight = float(
            r.get(C.SERVING_ROUTER_OCCUPANCY_WEIGHT,
                  C.SERVING_ROUTER_OCCUPANCY_WEIGHT_DEFAULT))
        self.breach_penalty = float(
            r.get(C.SERVING_ROUTER_BREACH_PENALTY,
                  C.SERVING_ROUTER_BREACH_PENALTY_DEFAULT))
        if self.replicas < 1:
            raise DeepSpeedConfigError(
                f"serving.router.replicas must be >= 1, got "
                f"{self.replicas}")
        for name in ("affinity_weight", "queue_weight",
                     "occupancy_weight", "breach_penalty"):
            if getattr(self, name) < 0:
                raise DeepSpeedConfigError(
                    f"serving.router.{name} must be >= 0, got "
                    f"{getattr(self, name)}")


class DeepSpeedServingConfig(DeepSpeedConfigObject):
    """``serving`` block (serving/): continuous-batching inference server
    over a paged KV cache. ``num_blocks`` 0 auto-sizes the pool so the
    full batch at full length fits (preemption-free); a smaller explicit
    pool trades HBM for preemption-by-eviction under pressure.
    ``max_model_len`` 0 defers to the served model's ``n_positions``.

    Env overrides (sweep ergonomics): ``DS_SERVING_MAX_BATCH`` /
    ``DS_SERVING_BLOCK_SIZE`` / ``DS_SERVING_PREFILL_CHUNK``."""

    def __init__(self, param_dict):
        s = param_dict.get(C.SERVING, {}) or {}
        self.block_size = int(s.get(C.SERVING_BLOCK_SIZE,
                                    C.SERVING_BLOCK_SIZE_DEFAULT))
        self.num_blocks = int(s.get(C.SERVING_NUM_BLOCKS,
                                    C.SERVING_NUM_BLOCKS_DEFAULT))
        self.max_batch = int(s.get(C.SERVING_MAX_BATCH,
                                   C.SERVING_MAX_BATCH_DEFAULT))
        self.prefill_chunk = int(s.get(C.SERVING_PREFILL_CHUNK,
                                       C.SERVING_PREFILL_CHUNK_DEFAULT))
        self.max_model_len = int(s.get(C.SERVING_MAX_MODEL_LEN,
                                       C.SERVING_MAX_MODEL_LEN_DEFAULT))
        self.decode_steps = int(s.get(C.SERVING_DECODE_STEPS,
                                      C.SERVING_DECODE_STEPS_DEFAULT))
        self.observability = DeepSpeedServingObservabilityConfig(s)
        self.prefix_cache = DeepSpeedServingPrefixCacheConfig(s)
        self.router = DeepSpeedServingRouterConfig(s)
        self.speculative = DeepSpeedServingSpeculativeConfig(s)
        for env, attr in (("DS_SERVING_MAX_BATCH", "max_batch"),
                          ("DS_SERVING_BLOCK_SIZE", "block_size"),
                          ("DS_SERVING_PREFILL_CHUNK", "prefill_chunk")):
            val = os.environ.get(env)
            if val is not None:
                setattr(self, attr, int(val))
        if self.block_size < 1:
            raise DeepSpeedConfigError(
                f"serving.block_size must be >= 1, got {self.block_size}")
        if self.max_batch < 1:
            raise DeepSpeedConfigError(
                f"serving.max_batch must be >= 1, got {self.max_batch}")
        if self.prefill_chunk < 1:
            raise DeepSpeedConfigError(
                f"serving.prefill_chunk must be >= 1, got "
                f"{self.prefill_chunk}")
        if self.num_blocks < 0 or self.num_blocks == 1:
            raise DeepSpeedConfigError(
                f"serving.num_blocks must be 0 (auto) or >= 2 (1 usable "
                f"+ the reserved null block), got {self.num_blocks}")
        if "attention_impl" in s:
            raise DeepSpeedConfigError(
                "serving.attention_impl was removed: the server has one "
                "attention path (a Pallas kernel for decode on a TPU over "
                "bfloat16 pools, one jnp walk elsewhere); delete the key")
        if self.decode_steps < 1:
            raise DeepSpeedConfigError(
                f"serving.decode_steps must be >= 1, got "
                f"{self.decode_steps}")


class DeepSpeedAutotuningConfig(DeepSpeedConfigObject):
    """``autotuning`` block (autotuning/tune.py): goodput-driven
    two-stage config search — compile-time pruning of the declared
    space, then measured probes of the top-K survivors scored by the
    goodput ledger. The block carries the tuner's defaults;
    ``GoodputTuner.from_config`` / the ``python -m
    deepspeed_tpu.autotuning.tune`` CLI consume it (the engine itself
    never autotunes mid-run).

    Env overrides (sweep ergonomics): ``DS_AUTOTUNING`` = 1/0
    force-toggles ``enabled``; ``DS_AUTOTUNING_TOP_K`` overrides
    ``top_k``; ``DS_AUTOTUNING_REPORT`` overrides ``report_file``."""

    def __init__(self, param_dict):
        a = param_dict.get(C.AUTOTUNING, {}) or {}
        self.enabled = a.get(C.AUTOTUNING_ENABLED,
                             C.AUTOTUNING_ENABLED_DEFAULT)
        self.metric = a.get(C.AUTOTUNING_METRIC, C.AUTOTUNING_METRIC_DEFAULT)
        self.top_k = int(a.get(C.AUTOTUNING_TOP_K,
                               C.AUTOTUNING_TOP_K_DEFAULT))
        self.probe_steps = int(a.get(C.AUTOTUNING_PROBE_STEPS,
                                     C.AUTOTUNING_PROBE_STEPS_DEFAULT))
        self.probe_warmup_steps = int(a.get(
            C.AUTOTUNING_PROBE_WARMUP, C.AUTOTUNING_PROBE_WARMUP_DEFAULT))
        self.memory_headroom = float(a.get(
            C.AUTOTUNING_MEMORY_HEADROOM,
            C.AUTOTUNING_MEMORY_HEADROOM_DEFAULT))
        self.hbm_budget_gb = float(a.get(C.AUTOTUNING_HBM_BUDGET_GB,
                                         C.AUTOTUNING_HBM_BUDGET_GB_DEFAULT))
        self.report_file = a.get(C.AUTOTUNING_REPORT_FILE,
                                 C.AUTOTUNING_REPORT_FILE_DEFAULT)
        self.results_dir = a.get(C.AUTOTUNING_RESULTS_DIR,
                                 C.AUTOTUNING_RESULTS_DIR_DEFAULT)
        self.seed = int(a.get(C.AUTOTUNING_SEED, C.AUTOTUNING_SEED_DEFAULT))
        self.space = a.get(C.AUTOTUNING_SPACE, C.AUTOTUNING_SPACE_DEFAULT)
        env = os.environ.get("DS_AUTOTUNING")
        if env is not None:
            self.enabled = env.lower() in ("1", "true", "yes", "on")
        env_k = os.environ.get("DS_AUTOTUNING_TOP_K")
        if env_k:
            self.top_k = int(env_k)
        env_r = os.environ.get("DS_AUTOTUNING_REPORT")
        if env_r:
            self.report_file = env_r
        if self.metric not in ("goodput", "step_time"):
            raise DeepSpeedConfigError(
                f"autotuning.metric must be 'goodput' or 'step_time', "
                f"got {self.metric!r}")
        if self.top_k < 1:
            raise DeepSpeedConfigError(
                f"autotuning.top_k must be >= 1, got {self.top_k}")
        if self.probe_steps < 1:
            raise DeepSpeedConfigError(
                f"autotuning.probe_steps must be >= 1, got "
                f"{self.probe_steps}")
        if self.probe_warmup_steps < 0:
            raise DeepSpeedConfigError(
                f"autotuning.probe_warmup_steps must be >= 0, got "
                f"{self.probe_warmup_steps}")
        if not 0.0 < self.memory_headroom <= 1.0:
            raise DeepSpeedConfigError(
                f"autotuning.memory_headroom must be in (0, 1], got "
                f"{self.memory_headroom}")
        if self.hbm_budget_gb < 0:
            raise DeepSpeedConfigError(
                f"autotuning.hbm_budget_gb must be >= 0 (0 = detect), "
                f"got {self.hbm_budget_gb}")
        if self.space is not None and (
                not isinstance(self.space, dict)
                or not all(isinstance(v, list) and v
                           for v in self.space.values())):
            raise DeepSpeedConfigError(
                "autotuning.space must map each dimension name to a "
                "non-empty list of values")


class DeepSpeedFlopsProfilerConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        fp = param_dict.get(C.FLOPS_PROFILER, {}) or {}
        self.enabled = fp.get(C.FLOPS_PROFILER_ENABLED, C.FLOPS_PROFILER_ENABLED_DEFAULT)
        self.profile_step = fp.get(C.FLOPS_PROFILER_PROFILE_STEP,
                                   C.FLOPS_PROFILER_PROFILE_STEP_DEFAULT)
        self.module_depth = fp.get(C.FLOPS_PROFILER_MODULE_DEPTH,
                                   C.FLOPS_PROFILER_MODULE_DEPTH_DEFAULT)
        self.top_modules = fp.get(C.FLOPS_PROFILER_TOP_MODULES,
                                  C.FLOPS_PROFILER_TOP_MODULES_DEFAULT)
        self.detailed = fp.get(C.FLOPS_PROFILER_DETAILED, C.FLOPS_PROFILER_DETAILED_DEFAULT)
        self.output_file = fp.get(C.FLOPS_PROFILER_OUTPUT_FILE,
                                  C.FLOPS_PROFILER_OUTPUT_FILE_DEFAULT)


class DeepSpeedActivationCheckpointingConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        ac = param_dict.get(C.ACTIVATION_CHECKPOINTING, {}) or {}
        self.partition_activations = ac.get(C.ACT_CHKPT_PARTITION_ACTIVATIONS,
                                            C.ACT_CHKPT_PARTITION_ACTIVATIONS_DEFAULT)
        self.number_checkpoints = ac.get(C.ACT_CHKPT_NUMBER_CHECKPOINTS,
                                         C.ACT_CHKPT_NUMBER_CHECKPOINTS_DEFAULT)
        self.contiguous_memory_optimization = ac.get(
            C.ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION,
            C.ACT_CHKPT_CONTIGUOUS_MEMORY_OPTIMIZATION_DEFAULT)
        self.synchronize_checkpoint_boundary = ac.get(
            C.ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY,
            C.ACT_CHKPT_SYNCHRONIZE_CHECKPOINT_BOUNDARY_DEFAULT)
        self.profile = ac.get(C.ACT_CHKPT_PROFILE, C.ACT_CHKPT_PROFILE_DEFAULT)
        self.cpu_checkpointing = ac.get(C.ACT_CHKPT_CPU_CHECKPOINTING,
                                        C.ACT_CHKPT_CPU_CHECKPOINTING_DEFAULT)


class DeepSpeedAIOConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        aio = param_dict.get(C.AIO, {}) or {}
        self.block_size = aio.get(C.AIO_BLOCK_SIZE, C.AIO_BLOCK_SIZE_DEFAULT)
        self.queue_depth = aio.get(C.AIO_QUEUE_DEPTH, C.AIO_QUEUE_DEPTH_DEFAULT)
        self.thread_count = aio.get(C.AIO_THREAD_COUNT, C.AIO_THREAD_COUNT_DEFAULT)
        self.single_submit = aio.get(C.AIO_SINGLE_SUBMIT, C.AIO_SINGLE_SUBMIT_DEFAULT)
        self.overlap_events = aio.get(C.AIO_OVERLAP_EVENTS, C.AIO_OVERLAP_EVENTS_DEFAULT)


class DeepSpeedEigenvalueConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        ev = param_dict.get(C.EIGENVALUE, {}) or {}
        self.enabled = ev.get(C.EIGENVALUE_ENABLED, C.EIGENVALUE_ENABLED_DEFAULT)
        self.verbose = ev.get(C.EIGENVALUE_VERBOSE, C.EIGENVALUE_VERBOSE_DEFAULT)
        self.max_iter = ev.get(C.EIGENVALUE_MAX_ITER, C.EIGENVALUE_MAX_ITER_DEFAULT)
        self.tol = ev.get(C.EIGENVALUE_TOL, C.EIGENVALUE_TOL_DEFAULT)
        self.stability = ev.get(C.EIGENVALUE_STABILITY, C.EIGENVALUE_STABILITY_DEFAULT)
        self.gas_boundary_resolution = ev.get(
            C.EIGENVALUE_GAS_BOUNDARY_RESOLUTION,
            C.EIGENVALUE_GAS_BOUNDARY_RESOLUTION_DEFAULT)
        self.layer_name = ev.get(C.EIGENVALUE_LAYER_NAME, C.EIGENVALUE_LAYER_NAME_DEFAULT)
        self.layer_num = ev.get(C.EIGENVALUE_LAYER_NUM, C.EIGENVALUE_LAYER_NUM_DEFAULT)


class DeepSpeedPLDConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        pld = param_dict.get(C.PROGRESSIVE_LAYER_DROP, {}) or {}
        self.enabled = pld.get(C.PLD_ENABLED, C.PLD_ENABLED_DEFAULT)
        self.theta = pld.get(C.PLD_THETA, C.PLD_THETA_DEFAULT)
        self.gamma = pld.get(C.PLD_GAMMA, C.PLD_GAMMA_DEFAULT)


class DeepSpeedCurriculumConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        cl = param_dict.get(C.CURRICULUM_LEARNING, {}) or {}
        self.enabled = cl.get(C.CURRICULUM_ENABLED, C.CURRICULUM_ENABLED_DEFAULT)
        self.params = {k: v for k, v in cl.items() if k != C.CURRICULUM_ENABLED}


class DeepSpeedQuantizeTrainingConfig(DeepSpeedConfigObject):
    """MoQ quantize-aware-training block (reference config.py:231-344)."""

    def __init__(self, param_dict):
        qt = param_dict.get(C.QUANTIZE_TRAINING, {}) or {}
        self.enabled = qt.get(C.QUANTIZE_TRAINING_ENABLED,
                              C.QUANTIZE_TRAINING_ENABLED_DEFAULT)
        bits = qt.get(C.QUANTIZE_BITS, {}) or {}
        self.start_bits = bits.get(C.START_BITS, C.START_BITS_DEFAULT)
        self.target_bits = bits.get(C.TARGET_BITS, C.TARGET_BITS_DEFAULT)
        sched = qt.get(C.QUANTIZE_SCHEDULE, {}) or {}
        self.quantize_period = sched.get(C.QUANTIZE_PERIOD, C.QUANTIZE_PERIOD_DEFAULT)
        self.schedule_offset = sched.get(C.SCHEDULE_OFFSET, C.SCHEDULE_OFFSET_DEFAULT)
        self.quantize_groups = qt.get(C.QUANTIZE_GROUPS, C.QUANTIZE_GROUPS_DEFAULT)
        self.quantize_verbose = qt.get(C.QUANTIZE_VERBOSE, C.QUANTIZE_VERBOSE_DEFAULT)
        self.quantizer_kernel = qt.get(C.QUANTIZER_KERNEL, C.QUANTIZER_KERNEL_DEFAULT)
        self.quantize_change_ratio = qt.get(C.QUANTIZE_CHANGE_RATIO,
                                            C.QUANTIZE_CHANGE_RATIO_DEFAULT)
        qtype = qt.get(C.QUANTIZE_TYPE, C.QUANTIZE_SYMMETRIC)
        self.quantize_type = qtype
        algo = qt.get(C.QUANTIZE_ALGO, {}) or {}
        self.rounding = algo.get(C.QUANTIZE_ROUNDING, "nearest")
        self.stochastic_rounding = self.rounding == "stochastic"
        mixed = qt.get(C.FP16_MIXED_QUANTIZE, {}) or {}
        self.fp16_mixed_quantize = mixed.get("enabled", False)
        self.quantize_offset = mixed.get(C.QUANTIZE_OFFSET, C.QUANTIZE_OFFSET_DEFAULT)


class DeepSpeedPipelineConfig(DeepSpeedConfigObject):
    def __init__(self, param_dict):
        p = param_dict.get(C.PIPELINE, {}) or {}
        self.stages = p.get(C.PIPELINE_STAGES, C.PIPELINE_STAGES_DEFAULT)
        self.partition = p.get(C.PIPELINE_PARTITION, C.PIPELINE_PARTITION_DEFAULT)
        self.seed_layers = p.get(C.PIPELINE_SEED_LAYERS, C.PIPELINE_SEED_LAYERS_DEFAULT)
        self.activation_checkpoint_interval = p.get(
            C.PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL,
            C.PIPELINE_ACTIVATION_CHECKPOINT_INTERVAL_DEFAULT)


class DeepSpeedConfig:
    """Top-level parsed config (reference DeepSpeedConfig, config.py:789)."""

    def __init__(self, config, mpu=None, data_parallel_size=None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(
                    f"DeepSpeed config file not found: {config}")
            with open(config) as f:
                self._param_dict = json.load(f)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        else:
            raise DeepSpeedConfigError(
                f"Expected a path or dict for the DeepSpeed config, got {type(config)}")

        # Data-parallel world for batch triangulation. Callers pass the real
        # dp degree; default 1 (single device).
        if data_parallel_size is None:
            if mpu is not None:
                data_parallel_size = mpu.get_data_parallel_world_size()
            else:
                data_parallel_size = 1
        self.world_size = data_parallel_size

        self._apply_elasticity(self._param_dict)
        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._do_sanity_check()

    def _apply_elasticity(self, pd):
        """When elasticity is enabled, take control of the batch parameters
        before triangulation (reference config.py:813-872): compute the
        elastic (final_batch_size, micro_batch) for this world size and
        override train_batch_size / micro_batch / gas in the param dict."""
        from deepspeed_tpu.elasticity import (compute_elastic_config,
                                              elasticity_enabled,
                                              ensure_immutable_elastic_config)
        from deepspeed_tpu.elasticity.elasticity import (
            ELASTICITY, IGNORE_NON_ELASTIC_BATCH_INFO,
            IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT)

        if not elasticity_enabled(pd):
            return
        logger.info("DeepSpeed elasticity support enabled")
        final_batch_size, valid_gpus, micro_batch_size = \
            compute_elastic_config(ds_config=pd, world_size=self.world_size)
        elastic_dict = pd[ELASTICITY]

        ensure_immutable_elastic_config(elastic_dict)

        if not elastic_dict.get(IGNORE_NON_ELASTIC_BATCH_INFO,
                                IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT):
            batch_params = [C.TRAIN_BATCH_SIZE,
                            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                            C.GRADIENT_ACCUMULATION_STEPS]
            if any(t in pd for t in batch_params):
                from deepspeed_tpu.elasticity import ElasticityConfigError
                raise ElasticityConfigError(
                    "One or more batch related parameters were found in your "
                    f"ds_config ({C.TRAIN_BATCH_SIZE}, "
                    f"{C.TRAIN_MICRO_BATCH_SIZE_PER_GPU}, and/or "
                    f"{C.GRADIENT_ACCUMULATION_STEPS}). These parameters "
                    "*will not be used* since elastic training is enabled, "
                    "which takes control of these parameters. If you want to "
                    "suppress this error (the parameters will be silently "
                    f"ignored) please set '{IGNORE_NON_ELASTIC_BATCH_INFO}'"
                    ":true in your elasticity config.")

        gradient_accu_steps = final_batch_size // (micro_batch_size *
                                                   self.world_size)
        for key, new in ((C.TRAIN_BATCH_SIZE, final_batch_size),
                         (C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, micro_batch_size),
                         (C.GRADIENT_ACCUMULATION_STEPS, gradient_accu_steps)):
            if key in pd:
                logger.warning(
                    f"[Elasticity] overriding {key}: {pd[key]} -> {new}")
            pd[key] = new
        logger.info(f"[Elasticity] valid chip counts: {valid_gpus}")
        self.elastic_valid_world_sizes = valid_gpus

    # -- parsing ------------------------------------------------------------

    def _initialize_params(self, pd):
        self.train_batch_size = pd.get(C.TRAIN_BATCH_SIZE, C.TRAIN_BATCH_SIZE_DEFAULT)
        self.train_micro_batch_size_per_gpu = pd.get(
            C.TRAIN_MICRO_BATCH_SIZE_PER_GPU, C.TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)
        self.gradient_accumulation_steps = pd.get(
            C.GRADIENT_ACCUMULATION_STEPS, C.GRADIENT_ACCUMULATION_STEPS_DEFAULT)
        self.steps_per_print = pd.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = pd.get(C.DUMP_STATE, C.DUMP_STATE_DEFAULT)

        self.disable_allgather = pd.get(C.DISABLE_ALLGATHER, C.DISABLE_ALLGATHER_DEFAULT)
        self.communication_data_type = pd.get(C.COMMUNICATION_DATA_TYPE,
                                              C.COMMUNICATION_DATA_TYPE_DEFAULT)
        self.prescale_gradients = pd.get(C.PRESCALE_GRADIENTS,
                                         C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_predivide_factor = pd.get(C.GRADIENT_PREDIVIDE_FACTOR,
                                                C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.sparse_gradients_enabled = pd.get(C.SPARSE_GRADIENTS,
                                               C.SPARSE_GRADIENTS_DEFAULT)

        self.zero_config = DeepSpeedZeroConfig.from_dict(pd)
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        self.fp16 = DeepSpeedFP16Config(pd)
        self.fp16_enabled = self.fp16.enabled
        self.bf16 = DeepSpeedBF16Config(pd)
        self.bfloat16_enabled = self.bf16.enabled
        self.fp16_master_weights_and_gradients = self.fp16.master_weights_and_grads
        self.amp_enabled = (pd.get(C.AMP, {}) or {}).get(C.AMP_ENABLED,
                                                         C.AMP_ENABLED_DEFAULT)
        self.amp_params = {k: v for k, v in (pd.get(C.AMP, {}) or {}).items()
                           if k != C.AMP_ENABLED}
        self.loss_scale = self.fp16.loss_scale
        self.initial_dynamic_scale = 2 ** self.fp16.initial_scale_power
        self.dynamic_loss_scale_args = {
            "init_scale": 2 ** self.fp16.initial_scale_power,
            "scale_window": self.fp16.loss_scale_window,
            "min_scale": self.fp16.min_loss_scale,
            "delayed_shift": self.fp16.hysteresis,
        }

        self.gradient_clipping = pd.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT)

        optimizer = pd.get(C.OPTIMIZER, {}) or {}
        self.optimizer_name = optimizer.get(C.TYPE, C.OPTIMIZER_TYPE_DEFAULT)
        if self.optimizer_name is not None and \
                self.optimizer_name.lower() in DEEPSPEED_OPTIMIZERS:
            self.optimizer_name = self.optimizer_name.lower()
        self.optimizer_params = optimizer.get(C.OPTIMIZER_PARAMS, None)
        self.optimizer_legacy_fusion = optimizer.get(C.LEGACY_FUSION,
                                                     C.LEGACY_FUSION_DEFAULT)
        self.zero_allow_untested_optimizer = pd.get(
            C.ZERO_ALLOW_UNTESTED_OPTIMIZER, C.ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT)

        scheduler = pd.get(C.SCHEDULER, {}) or {}
        self.scheduler_name = scheduler.get(C.TYPE, C.SCHEDULER_TYPE_DEFAULT)
        self.scheduler_params = scheduler.get(C.SCHEDULER_PARAMS, None)

        self.wall_clock_breakdown = pd.get(C.WALL_CLOCK_BREAKDOWN,
                                           C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = pd.get(C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT)
        self.tensorboard = DeepSpeedTensorboardConfig(pd)
        self.tensorboard_enabled = self.tensorboard.enabled
        self.tensorboard_output_path = self.tensorboard.output_path
        self.tensorboard_job_name = self.tensorboard.job_name
        self.telemetry = DeepSpeedTelemetryConfig(pd)
        self.telemetry_enabled = self.telemetry.enabled

        self.flops_profiler_config = DeepSpeedFlopsProfilerConfig(pd)
        self.activation_checkpointing_config = DeepSpeedActivationCheckpointingConfig(pd)
        self.aio_config = DeepSpeedAIOConfig(pd)
        self.eigenvalue_config = DeepSpeedEigenvalueConfig(pd)
        self.eigenvalue_enabled = self.eigenvalue_config.enabled
        self.pld_config = DeepSpeedPLDConfig(pd)
        self.pld_enabled = self.pld_config.enabled
        self.curriculum_config = DeepSpeedCurriculumConfig(pd)
        self.curriculum_enabled = self.curriculum_config.enabled
        self.quantize_training_config = DeepSpeedQuantizeTrainingConfig(pd)
        self.quantize_training_enabled = self.quantize_training_config.enabled
        self.pipeline_config = DeepSpeedPipelineConfig(pd)
        self.pipeline = pd.get(C.PIPELINE, {}) or {}

        self.sparse_attention = pd.get(C.SPARSE_ATTENTION, None)

        ckpt = pd.get(C.CHECKPOINT, {}) or {}
        self.checkpoint_tag_validation_mode = ckpt.get(
            C.CHECKPOINT_TAG_VALIDATION, C.CHECKPOINT_TAG_VALIDATION_DEFAULT)
        self.checkpoint_tag_validation_enabled = \
            self.checkpoint_tag_validation_mode != "Ignore"
        self.checkpoint_tag_validation_fail = \
            self.checkpoint_tag_validation_mode == "Fail"
        self.load_universal_checkpoint = ckpt.get(C.LOAD_UNIVERSAL_CHECKPOINT,
                                                  C.LOAD_UNIVERSAL_CHECKPOINT_DEFAULT)
        self.checkpoint_async_save = bool(ckpt.get(
            C.CHECKPOINT_ASYNC_SAVE, C.CHECKPOINT_ASYNC_SAVE_DEFAULT))
        self.checkpoint_fallback = bool(ckpt.get(
            C.CHECKPOINT_FALLBACK, C.CHECKPOINT_FALLBACK_DEFAULT))
        self.checkpoint_wait_timeout_s = float(ckpt.get(
            C.CHECKPOINT_WAIT_TIMEOUT, C.CHECKPOINT_WAIT_TIMEOUT_DEFAULT))
        self.checkpoint_persist_retries = int(ckpt.get(
            C.CHECKPOINT_PERSIST_RETRIES,
            C.CHECKPOINT_PERSIST_RETRIES_DEFAULT))
        self.checkpoint_persist_backoff_s = float(ckpt.get(
            C.CHECKPOINT_PERSIST_BACKOFF_S,
            C.CHECKPOINT_PERSIST_BACKOFF_S_DEFAULT))
        env_retries = os.environ.get("DS_CHECKPOINT_PERSIST_RETRIES")
        if env_retries is not None:
            self.checkpoint_persist_retries = int(env_retries)
        env_async = os.environ.get("DS_CHECKPOINT_ASYNC_SAVE")
        if env_async is not None:
            self.checkpoint_async_save = env_async.lower() in (
                "1", "true", "yes", "on")
        env_fb = os.environ.get("DS_CHECKPOINT_FALLBACK")
        if env_fb is not None:
            self.checkpoint_fallback = env_fb.lower() in (
                "1", "true", "yes", "on")
        if self.checkpoint_wait_timeout_s <= 0:
            raise DeepSpeedConfigError(
                f"checkpoint.{C.CHECKPOINT_WAIT_TIMEOUT} must be > 0, got "
                f"{self.checkpoint_wait_timeout_s}")
        if self.checkpoint_persist_retries < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.{C.CHECKPOINT_PERSIST_RETRIES} must be >= 0, "
                f"got {self.checkpoint_persist_retries}")
        if self.checkpoint_persist_backoff_s < 0:
            raise DeepSpeedConfigError(
                f"checkpoint.{C.CHECKPOINT_PERSIST_BACKOFF_S} must be "
                f">= 0, got {self.checkpoint_persist_backoff_s}")

        self.elasticity_enabled = bool((pd.get("elasticity", {}) or {}).get(
            "enabled", False))
        self.elasticity_params = pd.get("elasticity", {}) or {}

        # None = not configured. The engine's loader then defaults to
        # drop_last=True (a ragged final batch is a new shape, and under
        # jit a new shape is a recompile) — the reference's False default
        # is an eager-mode luxury; an EXPLICIT false is still honored.
        self.dataloader_drop_last = pd.get(C.DATALOADER_DROP_LAST, None)
        self.data_prefetch = DeepSpeedDataPrefetchConfig(pd)
        self.comm_overlap = DeepSpeedCommOverlapConfig(pd)
        self.guardian = DeepSpeedGuardianConfig(pd)
        self.serving = DeepSpeedServingConfig(pd)
        self.autotuning = DeepSpeedAutotuningConfig(pd)
        self.autotuning_enabled = self.autotuning.enabled
        self.gradient_accumulation_dtype = pd.get(C.GRADIENT_ACCUMULATION_FORMAT, None)

    # -- batch triangulation (reference config.py:926-1004) -----------------

    def _batch_assertion(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps
        if train_batch <= 0:
            raise DeepSpeedConfigError(f"Train batch size: {train_batch} has to be greater than 0")
        if micro_batch <= 0:
            raise DeepSpeedConfigError(f"Micro batch size per gpu: {micro_batch} has to be greater than 0")
        if grad_acc <= 0:
            raise DeepSpeedConfigError(f"Gradient accumulation steps: {grad_acc} has to be greater than 0")
        if train_batch != micro_batch * grad_acc * self.world_size:
            raise DeepSpeedConfigError(
                f"Check batch related parameters. train_batch_size is not equal "
                f"to micro_batch_per_gpu * gradient_acc_step * world_size "
                f"{train_batch} != {micro_batch} * {grad_acc} * {self.world_size}")

    def _set_batch_related_parameters(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps

        # All three provided: verify below. Otherwise derive missing ones.
        if train_batch is not None and micro_batch is not None and grad_acc is not None:
            pass
        elif train_batch is not None and micro_batch is not None:
            grad_acc = train_batch // micro_batch
            grad_acc //= self.world_size
            self.gradient_accumulation_steps = grad_acc
        elif train_batch is not None and grad_acc is not None:
            micro_batch = train_batch // self.world_size
            micro_batch //= grad_acc
            self.train_micro_batch_size_per_gpu = micro_batch
        elif micro_batch is not None and grad_acc is not None:
            self.train_batch_size = micro_batch * grad_acc * self.world_size
        elif train_batch is not None:
            self.gradient_accumulation_steps = 1
            self.train_micro_batch_size_per_gpu = train_batch // self.world_size
        elif micro_batch is not None:
            self.train_batch_size = micro_batch * self.world_size
            self.gradient_accumulation_steps = 1
        else:
            raise DeepSpeedConfigError(
                "Either train_batch_size or train_micro_batch_size_per_gpu "
                "needs to be provided")

    def _configure_train_batch_size(self):
        self._set_batch_related_parameters()
        self._batch_assertion()

    # -- sanity checks (reference config.py:1033-1090) -----------------------

    def _do_sanity_check(self):
        if self.optimizer_name is not None and self.zero_enabled:
            if (self.optimizer_name not in DEEPSPEED_OPTIMIZERS
                    and not self.zero_allow_untested_optimizer):
                raise DeepSpeedConfigError(
                    f"ZeRO is only supported with DeepSpeed optimizers "
                    f"{DEEPSPEED_OPTIMIZERS}; set zero_allow_untested_optimizer "
                    f"to force-enable '{self.optimizer_name}'")
        if self.fp16_enabled and self.bfloat16_enabled:
            raise DeepSpeedConfigError("fp16 and bf16 modes are mutually exclusive")
        if self.fp16_master_weights_and_gradients:
            raise DeepSpeedConfigError(
                "fp16_master_weights_and_grads halves HOST memory for the "
                "cpu-offload masters; the TPU offload engine keeps fp32 "
                "masters (host RAM is not the binding constraint on TPU "
                "hosts, and the AVX CPU-Adam operates on fp32 buffers) — "
                "remove the key")
        # -- no-silent-no-op rule (same as the pipeline/offload dispatch in
        # deepspeed_tpu/__init__.py): keys whose reference mechanism has no
        # TPU/XLA counterpart are REJECTED when set off-default, never
        # silently accepted.
        if self.amp_enabled:
            raise DeepSpeedConfigError(
                "amp.enabled: NVIDIA apex AMP has no TPU counterpart; use "
                "the native mixed-precision blocks instead — bf16 "
                "{enabled: true} (preferred on TPU) or fp16 {enabled: true}")
        if self.prescale_gradients or self.gradient_predivide_factor != 1.0:
            raise DeepSpeedConfigError(
                "prescale_gradients/gradient_predivide_factor rescale "
                "gradients around an explicit NCCL allreduce to dodge fp16 "
                "overflow; under XLA the data-parallel reduction is fused "
                "into the compiled step with fp32 accumulation, so there "
                "is no allreduce boundary to pre-scale — remove the key "
                "(fp16 overflow is handled by the dynamic loss scaler)")
        if self.disable_allgather:
            raise DeepSpeedConfigError(
                "disable_allgather selects allreduce over allgather for "
                "the ZeRO-1 parameter update; XLA chooses the collective "
                "implementation from the sharding layout — remove the key")
        if self.communication_data_type is not None:
            raise DeepSpeedConfigError(
                "communication_data_type casts gradients for an explicit "
                "allreduce; XLA's fused reduction accumulates in fp32 and "
                "there is no user-visible collective to cast — remove the "
                "key (for bandwidth compression use the 1-bit optimizers)")
        if self.optimizer_legacy_fusion:
            raise DeepSpeedConfigError(
                "optimizer.legacy_fusion toggles a CUDA kernel-fusion "
                "fallback; TPU optimizers are XLA/Pallas-fused uncondition"
                "ally — remove the key")
        if self.gradient_accumulation_dtype not in (
                None, "fp32", "bf16", "fp16"):
            raise DeepSpeedConfigError(
                "data_types.grad_accum_dtype must be one of "
                "fp32|bf16|fp16, got "
                f"{self.gradient_accumulation_dtype!r}")

    def print(self, name="DeepSpeedConfig"):
        logger.info(f"{name}:")
        logger.info(json.dumps(self._param_dict, sort_keys=True, indent=4))
