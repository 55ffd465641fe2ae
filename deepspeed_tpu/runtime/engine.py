"""The training engine.

TPU-native rebuild of ``DeepSpeedEngine`` (reference
deepspeed/runtime/engine.py:165). The reference wraps an eager PyTorch
module and imperatively orchestrates precision, ZeRO hooks, collectives and
the optimizer across ``forward``/``backward``/``step``. Here the same user
surface drives ONE pjit-compiled micro-step and ONE compiled apply-step
over a named device mesh:

* ``forward(batch)`` computes the (scaled) loss AND the gradients in a
  single fused compiled call, accumulating fp32 grads into the train state
  (the reference's separate backward exists because autograd is eager; in
  JAX loss and grads come from one ``value_and_grad``). ``backward()``
  advances the micro-step counter; ``step()`` applies the optimizer at the
  gradient-accumulation boundary — matching the reference's
  ``is_gradient_accumulation_boundary`` semantics (engine.py:1747).
* ZeRO stages are sharding rules (runtime/zero/partition.py), not hooks:
  the state carries NamedShardings and XLA inserts the all-gather /
  reduce-scatter traffic that stage_1_and_2.py / stage3.py issue by hand.
* Mixed precision: fp32 master params live in the state; the forward casts
  to bf16/fp16 (``_configure_distributed_model`` engine.py:997 analogue);
  dynamic loss scaling runs inside the compiled step with a ``lax.cond``
  skip — no per-step host sync (reference overflow check engine.py:1747+
  forces D2H).

Checkpoint layout keeps the reference's file naming
(``{tag}/mp_rank_00_model_states.pt``, ``zero_pp_rank_*_optim_states.pt``,
``latest`` tag file — engine.py:2350/:2345/:2889) so downstream tooling and
the zero_to_fp32 converter work unchanged.
"""

import contextlib
import glob
import os
import shutil
import time
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.runtime import optim as optim_lib
from deepspeed_tpu.runtime.config import (
    ADAGRAD_OPTIMIZER, ADAM_OPTIMIZER, ADAMW_OPTIMIZER, DeepSpeedConfig,
    LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER, ONEBIT_LAMB_OPTIMIZER, SGD_OPTIMIZER)
from deepspeed_tpu.runtime.constants import ROUTE_TRAIN
from deepspeed_tpu.runtime.dataloader import DeepSpeedDataLoader, RepeatingLoader
from deepspeed_tpu.runtime.prefetch import PrefetchIterator, PrefetchLoader
from deepspeed_tpu.runtime.fp16.loss_scaler import (
    LossScaleState, make_scale_state, scale_state_stats, update_scale)
from deepspeed_tpu.runtime.lr_schedules import get_lr_schedule
from deepspeed_tpu.runtime.zero.partition import (
    ModelParallelRules, build_opt_shardings, build_param_shardings,
    grad_constraint_fn)
from deepspeed_tpu.utils import groups
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer, ThroughputTimer

# reference timer names (deepspeed/runtime/engine.py:113-123). Under XLA the
# forward and backward are ONE fused vjp program, so the 'forward' timer
# carries the fused fwd+bwd time and 'backward' only the host bookkeeping;
# a one-time log line says so when wall_clock_breakdown is enabled.
FORWARD_GLOBAL_TIMER = "forward"
BACKWARD_GLOBAL_TIMER = "backward"
STEP_GLOBAL_TIMER = "step"

MODEL_FILE_SUFFIX = "_model_states.pt"
OPTIM_FILE_SUFFIX = "_optim_states.pt"
LATEST_FILE = "latest"

# shared no-op for the goodput-disabled ledger paths (nullcontext holds no
# state, so one instance can nest/re-enter freely)
_NULL_CTX = contextlib.nullcontext()


class TrainState(NamedTuple):
    """All mutable training state, as one donated pytree.

    ``step`` counts APPLIED (non-skipped) optimizer steps — it indexes the
    LR schedule inside the compiled apply step. Micro-step and skipped-step
    counters live host-side only (self.micro_steps / self.skipped_steps);
    keeping device copies would create a second source of truth."""
    step: jnp.ndarray          # applied optimizer steps
    params: Any                # fp32 master parameters
    opt_state: Any
    acc_grads: Any             # fp32 accumulation buffer (ZeRO-sharded)
    scale: LossScaleState


def _cast_tree(tree, dtype):
    return jax.tree.map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating) else x,
        tree)


def _default_sparse_ids_fn(batch):
    """Token ids whose embedding rows the batch touches (reference: the
    indices of the torch sparse embedding grad)."""
    if isinstance(batch, dict):
        for k in ("input_ids", "ids", "tokens"):
            if k in batch:
                return batch[k]
        raise ValueError(
            "sparse_gradients: could not find token ids in the batch dict "
            f"(keys {list(batch)}); pass sparse_ids_fn=... to initialize()")
    if isinstance(batch, (tuple, list)):
        ids = batch[0]
    else:
        ids = batch
    if not jnp.issubdtype(jnp.asarray(ids).dtype, jnp.integer):
        raise ValueError(
            "sparse_gradients: the first batch element has dtype "
            f"{jnp.asarray(ids).dtype}, not an integer token-id array; "
            "pass sparse_ids_fn=... to initialize()")
    return ids


class _AOTStep:
    """AOT execution wrapper around ONE jitted step entry point.

    When the cost explorer is enabled, the engine's first dispatch for a
    signature goes ``lower -> compile -> call`` so the
    ``jax.stages.Compiled`` artifact is KEPT — the same single compile
    the jit would have done, but ``cost_analysis()`` /
    ``memory_analysis()`` / ``as_text()`` stay readable at zero cost, and
    the HBM pre-flight can run BETWEEN compile and first execution. (The
    wrapper was written when the eager-jit and AOT executable caches
    were disjoint and the AOT path paid a duplicate XLA compile. Under
    the installed jax 0.9 ``lower().compile()`` of a signature that has
    already run is served from the same cache — re-checked in PR 21 —
    so only the pre-flight ordering still needs this ownership.)

    Per-call cost is one tree_flatten signature check (~µs, measured
    +0.6µs vs the raw jit fastpath) — only paid when the cost explorer
    is explicitly enabled. A NEW signature after priming (curriculum
    plateau, eval shape) falls back to the wrapped jit, which retraces
    exactly as before.
    """

    def __init__(self, jit_fn, name, on_compiled=None):
        self._jit = jit_fn
        self._name = name
        self._on_compiled = on_compiled      # callback(name, compiled)
        self._sig = None
        self.compiled = None                 # jax.stages.Compiled once primed
        self._prime_failed = False
        self.fallback_calls = 0
        # unwrap contract: consumers (flops profiler) expect __wrapped__
        # to be the RAW python function, as on the jit itself
        self.__wrapped__ = getattr(jit_fn, "__wrapped__", jit_fn)
        self.__name__ = name

    def lower(self, *args, **kwargs):
        """AOT surface, delegated (lower_train_step-style consumers)."""
        return self._jit.lower(*args, **kwargs)

    def _signature(self, args):
        leaves, treedef = jax.tree_util.tree_flatten(args)
        if any(isinstance(x, jax.core.Tracer) for x in leaves):
            # being traced by an outer transformation (module profiler's
            # jaxpr walk): a Compiled cannot be transformed — the wrapped
            # jit inlines fine, so route there via the sig-less fallback
            return None
        # sharding is None for UNCOMMITTED arrays: like the jit, the
        # Compiled places them to match the executable, so they must not
        # constrain the match (load_checkpoint rebuilds scalar state
        # leaves uncommitted — exact-sharding matching would dump those
        # steps onto the cold fallback jit and pay a fresh compile)
        return (treedef, tuple(
            (getattr(x, "shape", None), getattr(x, "dtype", None),
             getattr(x, "sharding", None)
             if getattr(x, "committed", True) else None) for x in leaves))

    def _matches(self, sig):
        if self._sig is None or sig is None:
            return False
        if sig == self._sig:
            return True
        treedef, leaves = sig
        ptreedef, pleaves = self._sig
        if treedef != ptreedef or len(leaves) != len(pleaves):
            return False
        for (shp, dt, sh), (pshp, pdt, psh) in zip(leaves, pleaves):
            if shp != pshp or dt != pdt:
                return False
            if sh is not None and psh is not None and sh != psh:
                return False
        return True

    def __call__(self, *args):
        try:
            sig = self._signature(args)
        except Exception:
            sig = None
        if self.compiled is not None and self._matches(sig):
            return self.compiled(*args)
        if sig is not None and self.compiled is None \
                and not self._prime_failed:
            try:
                compiled = self._jit.lower(*args).compile()
            except Exception as e:
                logger.warning(
                    "[cost-explorer] AOT compile of %r failed (%s); "
                    "falling back to the plain jit path — explain_step "
                    "will pay a duplicate compile", self._name, e)
                self._prime_failed = True    # never retry priming
                return self._jit(*args)
            self.compiled, self._sig = compiled, sig
            if self._on_compiled is not None:
                try:
                    self._on_compiled(self._name, compiled)
                except Exception as e:       # census must never kill a step
                    logger.warning(
                        "[cost-explorer] census hook for %r failed: %s",
                        self._name, e)
            return compiled(*args)
        self.fallback_calls += 1
        return self._jit(*args)


class DeepSpeedEngine:
    """See module docstring. Constructed via ``deepspeed_tpu.initialize``."""

    def __init__(self,
                 args=None,
                 model=None,
                 optimizer=None,
                 model_parameters=None,
                 training_data=None,
                 lr_scheduler=None,
                 mpu=None,
                 dist_init_required=None,
                 collate_fn=None,
                 config=None,
                 config_params=None,
                 loss_fn=None,
                 sample_batch=None,
                 mp_rules=None,
                 batch_spec=None,
                 dont_change_device=False,
                 sparse_embedding_rules=None,
                 sparse_ids_fn=None,
                 seed=42,
                 abstract_init=False):
        import deepspeed_tpu.comm as dist
        dist.init_distributed(verbose=False)

        self.module = model
        self.model = model
        self.loss_fn = loss_fn
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        # batch PartitionSpec override — sequence-parallel runs shard the
        # SEQ dim of the batch over a mesh axis instead of the batch dim
        # (ops/transformer/ring.py)
        self._batch_spec = batch_spec
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self._seed = seed
        # abstract_init: build every step function against
        # ShapeDtypeStructs WITHOUT materialising params/optimizer state —
        # the AOT-lowering mode that proves a config's sharded program
        # builds at true scale (lower_train_step) on meshes far larger
        # than this host could hold in memory
        self._abstract_init = abstract_init

        # ---- mesh (reference: groups.initialize, engine.py:1031) ----------
        if not groups.mesh_is_initialized():
            groups.initialize(mpu=mpu)
        self.mesh = groups.get_mesh()
        self.dp_world_size = groups.get_data_parallel_world_size()
        self.mp_world_size = groups.get_model_parallel_world_size()

        # ---- config -------------------------------------------------------
        if config is None and config_params is not None:
            config = config_params
        if config is None and args is not None:
            config = getattr(args, "deepspeed_config", None)
        assert config is not None, "DeepSpeed requires --deepspeed_config or config dict"
        if isinstance(config, DeepSpeedConfig):
            assert config.world_size == self.dp_world_size, (
                f"pre-built DeepSpeedConfig was triangulated for data-parallel "
                f"world {config.world_size}, but the mesh has {self.dp_world_size}")
            self.config = config
        else:
            self.config = DeepSpeedConfig(config, mpu=None,
                                          data_parallel_size=self.dp_world_size)

        self.zero_stage = self.config.zero_optimization_stage
        self.mp_rules = mp_rules or ModelParallelRules()
        # ZeRO-Offload: optimizer state leaves HBM for host RAM / NVMe
        # (reference cpu_offload stage_1_and_2.py:1003, stage3 swapping)
        self._offload_device = self.config.zero_config.offload_optimizer.device
        self._offload = self._offload_device not in (None, "none")
        self._offload_opt = None
        # set by _configure_optimizer when a 1-bit optimizer runs with the
        # REAL compressed collective (dp > 1): step fns then keep grads
        # rank-local under shard_map (_build_onebit_step_fns)
        self._onebit_dist = False
        # broadcast batch leaves checksum-verified across processes, by
        # (path, shape, dtype) — first occurrence only (_globalize_batch)
        self._broadcast_leaves_checked = set()

        # ---- precision ----------------------------------------------------
        if self.config.fp16_enabled:
            self.compute_dtype = jnp.float16
        elif self.config.bfloat16_enabled:
            self.compute_dtype = jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self._dynamic_scale = (self.config.fp16_enabled
                               and self.config.fp16.dynamic_loss_scale)
        if self.config.fp16_enabled:
            init_scale = (self.config.initial_dynamic_scale
                          if self._dynamic_scale else self.config.loss_scale)
        else:
            init_scale = 1.0
        self._init_scale = float(init_scale)

        # ---- optimizer (reference _configure_basic_optimizer, :1163) ------
        self.optimizer = self._configure_optimizer()

        # ---- sparse embedding gradients (reference engine.py:2196-2268:
        # "sparse_gradients": true ships (indices, values) rows instead of
        # the dense [V, D] embedding grad over the DP group). Like the
        # reference — where only modules explicitly constructed sparse
        # (nn.Embedding(sparse=True)) produce sparse grads — the tables
        # must be DECLARED via sparse_embedding_rules: a declared table's
        # gradient must be supported on the batch's token rows only (an
        # untied lookup table indexed by sparse_ids_fn(batch)). Tied
        # LM-head tables or position/type tables have dense (or
        # differently-indexed) grads and must NOT be declared.
        self._sparse_grad_rules = tuple(sparse_embedding_rules or ())
        self._sparse_ids_fn = sparse_ids_fn or _default_sparse_ids_fn
        self._sparse_grads = (bool(self.config.sparse_gradients_enabled)
                              and self.dp_world_size > 1
                              and not self._onebit_dist)
        if self._sparse_grads and not self._sparse_grad_rules:
            logger.warning(
                "sparse_gradients is enabled but no sparse embedding "
                "tables are declared; pass sparse_embedding_rules=[...] "
                "to initialize() (regexes over param paths of untied, "
                "input-id-indexed lookup tables). Falling back to dense "
                "gradient reduction.")
            self._sparse_grads = False
        if self._sparse_grads:
            bad = []
            if self.zero_stage >= 2:
                # stage>=2 grads live reduce-scattered — the reference has
                # the same envelope (sparse handled only on the
                # buffered_allreduce_fallback path, engine.py:1648)
                bad.append(f"zero stage {self.zero_stage} (need <= 1)")
            if self.mp_world_size != 1:
                bad.append("model parallelism (embedding may be sharded)")
            if self._batch_spec is not None:
                bad.append("custom batch_spec (need the batch dim sharded "
                           "over the data axis)")
            if groups.get_expert_parallel_world_size() != 1:
                bad.append("expert parallelism (shard_map maps only the "
                           "data axis)")
            if groups.get_pipe_parallel_world_size() != 1:
                bad.append("pipeline parallelism")
            if bad:
                raise ValueError("sparse_gradients is incompatible with: "
                                 + "; ".join(bad))

        # ---- comm overlap (runtime/comm_overlap.py) -----------------------
        # bucketed gradient reduction: resolved in _build_step_fns (after
        # the sparse mask can still fall back to dense) so the variant is
        # selected BEFORE the first lower, like the health stats variant
        self._comm_overlap_cfg = self.config.comm_overlap
        self._comm_overlap_on = False
        self._overlap_spec = None
        self._warned_comm_overlap = False

        # ---- lr schedule (reference _configure_lr_scheduler, :790) --------
        self.lr_scheduler, self._lr_fn, self._base_lr = self._configure_lr_scheduler()

        # ---- aux trainers: PLD, curriculum, MoQ (reference engine.py
        # :1571-1583 forward kwarg injection; :1816-1827 MoQ step hook) ----
        self.progressive_layer_drop = None
        if self.config.pld_enabled:
            from deepspeed_tpu.runtime.progressive_layer_drop import \
                ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=self.config.pld_config.theta,
                gamma=self.config.pld_config.gamma)
        self.curriculum_scheduler = None
        if self.config.curriculum_enabled:
            from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler \
                import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler(
                self.config.curriculum_config.params)
        self.quantizer = None
        ev_cfg = self.config.eigenvalue_config
        if getattr(self.config, "quantize_training_enabled", False):
            from deepspeed_tpu.runtime.quantize import Quantizer
            qc = self.config.quantize_training_config
            self.quantizer = Quantizer(
                q_groups=qc.quantize_groups,
                q_mixed_fp16=qc.fp16_mixed_quantize,
                q_change_ratio=qc.quantize_change_ratio,
                q_type=0 if qc.quantize_type == "symmetric" else 1,
                q_rounding=1 if getattr(qc, "rounding", "nearest") ==
                "stochastic" else 0,
                q_start_bits=qc.start_bits, q_target_bits=qc.target_bits,
                q_period=qc.quantize_period,
                q_eigenvalue=self.config.eigenvalue_enabled,
                layer_num=ev_cfg.layer_num if
                self.config.eigenvalue_enabled else 0)
        # eigenvalue-guided MoQ (reference engine.py:316 construction,
        # :1891 per-step block_eigenvalue feed)
        self.eigenvalue = None
        self.block_eigenvalue = {}
        if self.config.eigenvalue_enabled:
            if self.quantizer is None:
                raise ValueError(
                    "eigenvalue.enabled=true has no consumer without "
                    "quantize_training (MoQ): the curvature estimate only "
                    "guides the quantization schedule — enable "
                    "quantize_training or drop the eigenvalue block")
            if ev_cfg.layer_num < 1:
                raise ValueError(
                    "eigenvalue.layer_num must be the model's repeated-"
                    "layer count (>= 1): it sizes the per-block MoQ "
                    "schedule and bounds the block ids parsed from param "
                    "paths")
            from deepspeed_tpu.runtime.eigenvalue import Eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=ev_cfg.verbose, max_iter=ev_cfg.max_iter,
                tol=ev_cfg.tol, stability=ev_cfg.stability,
                gas_boundary_resolution=ev_cfg.gas_boundary_resolution,
                layer_name=ev_cfg.layer_name, layer_num=ev_cfg.layer_num)

        # ---- telemetry (telemetry/: spans, compile watch, metrics) --------
        # built BEFORE state init so the init work is traceable and the
        # compiled entry points can be compile-watch wrapped right after
        # _build_step_fns constructs them. Rank-0 only; every surface is a
        # no-op when the config block is absent/disabled.
        from deepspeed_tpu.telemetry import TelemetryManager, get_registry
        from deepspeed_tpu.telemetry.tracer import watch_gc
        self.telemetry = TelemetryManager(self.config.telemetry,
                                          rank=dist.get_rank())
        # this loop owns the process's garbage collections until close()
        self._gc = watch_gc("train",
                            self.telemetry.registry or get_registry(), self)

        # ---- goodput ledger (telemetry/ledger.py) -------------------------
        # Host-side wall-clock attribution only — it never changes the
        # compiled programs and never syncs the device, so (unlike the
        # health stats variant) rank-0-only gating through the manager is
        # safe. None when disabled; every call site is None-checked.
        self._goodput = getattr(self.telemetry, "goodput", None)
        self._goodput_cadence = int(
            getattr(self.config.telemetry, "goodput_cadence", 0) or 0)

        # ---- cost explorer (telemetry/cost_explorer.py) -------------------
        # gated on the CONFIG (not the rank-0-only manager) so every rank
        # dispatches through the same _AOTStep code path; census gauges and
        # pre-flight warnings still publish on rank 0 only (the manager's
        # registry is the gate). abstract_init engines never execute, so
        # there is no artifact to own — lower_train_step covers them.
        tcfg = self.config.telemetry
        self._cost_explorer_on = (
            bool(getattr(tcfg, "enabled", False))
            and bool(getattr(tcfg, "cost_explorer_enabled", False))
            and not self._abstract_init)
        self._cost_census = None
        self._cost_census_program = None
        self._first_step_time_ms = None

        # ---- training-health observatory (telemetry/health.py) ------------
        # Like the cost explorer, gated on the CONFIG (not the rank-0-only
        # manager): the stats variant changes the compiled step program, so
        # every rank must build the same one. The host-side HealthMonitor
        # (anomaly rules, HEALTH.json) lives on rank 0 only, inside the
        # manager. abstract_init engines never execute a step.
        self._health_on = (bool(getattr(tcfg, "enabled", False))
                           and bool(getattr(tcfg, "health_enabled", False))
                           and not self._abstract_init)
        self._health_cadence = int(getattr(tcfg, "health_cadence", 0) or 0)
        self._health_spec = None

        # ---- HBM residency observatory (telemetry/memory_observatory) -----
        # Host-side only — the cadence tick fetches the runtime's own
        # allocator bookkeeping (device_memory_profile is a host RPC, not
        # a program change or a device sync), so rank-0-only gating
        # through the manager is safe, like goodput.
        self._memory = getattr(self.telemetry, "memory", None)
        self._memory_cadence = int(getattr(tcfg, "memory_cadence", 0) or 0)
        self._memory_last_obs_step = -1
        self._memory_inventory = None    # cached expected-bytes accounting
        self._memory_budget_checked = False
        self._memory_warned_fetch = False

        # ---- fleet flight recorder (telemetry/fleet.py) -------------------
        # Cross-rank by design: the SHIPPER runs on EVERY rank (per-rank
        # window records into the shared run dir are the whole point), so
        # it is gated on the CONFIG, not the rank-0-only manager. The
        # aggregating MONITOR (skew/desync sentinels, FLEET_HEALTH.json)
        # lives on fleet rank 0 only. The desync checksum program is armed
        # later, in _build_step_fns, once the param tree exists.
        self._fleet = None
        self._fleet_monitor = None
        self._fleet_cadence = 0
        self._fleet_ticks = 0
        self._desync_on = False
        self._desync_every = 1
        self._desync_fn = None
        self._desync_spec = None
        self._warned_desync = False
        if (bool(getattr(tcfg, "enabled", False))
                and bool(getattr(tcfg, "fleet_enabled", False))
                and not self._abstract_init):
            from deepspeed_tpu.telemetry import fleet as _fleet_mod
            frank = int(getattr(tcfg, "fleet_rank", -1))
            if frank < 0:
                frank = dist.get_rank()
            fleet_run_dir = getattr(tcfg, "fleet_run_dir", "") or \
                os.path.join(tcfg.output_path or "telemetry/", "fleet_run")
            self._fleet_cadence = int(getattr(tcfg, "fleet_cadence", 0)
                                      or 0)
            self._desync_every = max(
                1, int(getattr(tcfg, "fleet_desync_cadence", 0) or 1))
            self._fleet = _fleet_mod.FleetShipper(
                fleet_run_dir, rank=frank,
                job_name=tcfg.job_name or "",
                background=bool(getattr(tcfg, "fleet_background_ship",
                                        True)))
            _fleet_mod.set_shipper(self._fleet)
            if self._goodput is not None:
                # window categories come from this rank's own ledger as
                # exact integer-µs diffs; ranks without a ledger fall
                # back to the shipper's own input-wait/checkpoint timers
                self._fleet.attach_ledger(self._goodput)
            if frank == 0:
                self._fleet_monitor = _fleet_mod.FleetMonitor.from_config(
                    tcfg, run_dir=fleet_run_dir,
                    output_path=tcfg.output_path or "telemetry/",
                    job_name=tcfg.job_name or "",
                    registry=self.telemetry.registry,
                    on_escalate=(self.telemetry._force_trace_export
                                 if self.telemetry.enabled and tcfg.trace
                                 else None))
            if self.telemetry.enabled and self.telemetry.tracer.enabled:
                # rank-tagged process metadata: per-rank trace files
                # concatenate into one per-rank-lane view (fleet.py's
                # merge_traces / --merge-traces)
                self.telemetry.tracer.set_process_label(
                    f"rank {frank}", sort_index=frank)

        # ---- run chronicle (telemetry/chronicle.py) -----------------------
        # The causal event timeline every subsystem emits into. Per-rank
        # by design (one atomic JSONL stream per rank in the run dir), so
        # gated on the CONFIG like the fleet shipper, not the rank-0-only
        # manager. Armed BEFORE the guardian so its first action lands in
        # the timeline.
        self._chronicle = None
        self._chronicle_summary_path = None
        self._chronicle_incidents_path = None
        if (bool(getattr(tcfg, "enabled", False))
                and bool(getattr(tcfg, "chronicle_enabled", False))
                and not self._abstract_init):
            from deepspeed_tpu.telemetry import chronicle as _chron_mod
            _chron_out = tcfg.output_path or "telemetry/"
            chron_run_dir = getattr(tcfg, "chronicle_run_dir", "") or \
                os.path.join(_chron_out, "chronicle")
            self._chronicle_summary_path = \
                getattr(tcfg, "chronicle_summary_file", "") or \
                os.path.join(_chron_out, "CHRONICLE.json")
            self._chronicle_incidents_path = \
                getattr(tcfg, "chronicle_incidents_file", "") or \
                os.path.join(_chron_out, "INCIDENTS.json")
            self._chronicle = _chron_mod.RunChronicle(
                run_dir=chron_run_dir, rank=dist.get_rank(),
                job_name=tcfg.job_name or "",
                max_events=int(getattr(tcfg, "chronicle_max_events",
                                       16384)),
                background=bool(getattr(tcfg, "chronicle_background",
                                        True)))
            _chron_mod.set_chronicle(self._chronicle)
        self._chronicle_first_emitted = False

        # ---- self-healing guardian (runtime/guardian.py) ------------------
        # anomaly->action policy engine: the monitors above classify and
        # escalate; the guardian (when armed) subscribes to their
        # on_anomaly hooks and performs bounded actions — emergency
        # checkpoint, rollback, fp16 rescue, serving admission pause.
        # Single-process only for now: a rollback swaps the LIVE train
        # state, and coordinating that across ranks is the multi-replica
        # failover item on the roadmap (this substrate feeds it).
        self._guardian = None
        self._guardian_ckpt_dir = None      # learned from save_checkpoint
        self._guardian_data_iter = None     # learned from train_batch
        gcfg = self.config.guardian
        if bool(getattr(gcfg, "enabled", False)) and not self._abstract_init:
            if dist.get_process_count() > 1:
                logger.warning(
                    "[guardian] enabled but running multi-process; the "
                    "guardian's rollback/rescue actions are single-process "
                    "only — disarming (cross-rank healing is the fleet "
                    "failover roadmap item)")
            else:
                from deepspeed_tpu.runtime.guardian import Guardian
                self._guardian = Guardian.from_config(
                    gcfg, output_path=tcfg.output_path or "telemetry/",
                    job_name=tcfg.job_name or "",
                    registry=self.telemetry.registry)
                self._guardian.emergency_save_fn = \
                    self._guardian_emergency_save
                self._guardian.rollback_fn = self._guardian_rollback
                self._guardian.fp16_rescue_fn = self._guardian_fp16_rescue
                # subscribe to every armed monitor's action hook (the
                # serving observatory is wired by ServingEngine, which
                # shares this instance)
                if self.telemetry.health is not None:
                    self.telemetry.health.on_anomaly = \
                        self._guardian.hook("health")
                if self._goodput is not None:
                    self._goodput.on_anomaly = self._guardian.hook("goodput")
                if self._fleet_monitor is not None:
                    self._fleet_monitor.on_anomaly = \
                        self._guardian.hook("fleet")
                if self._memory is not None:
                    self._memory.on_anomaly = self._guardian.hook("memory")

        # ---- SLO burn-rate monitor (telemetry/slo.py) ---------------------
        # multi-window error-budget alerting over the ledger and the
        # registry histograms — pure host bookkeeping, gated on the
        # rank-0 telemetry manager like the monitors it reads. The
        # page-tier rule (slo_burn_page) is a guardian admission-pause
        # rule, so a sustained burn sheds serving load by itself.
        self._slo = None
        if (self.telemetry.enabled
                and bool(getattr(tcfg, "slo_enabled", False))
                and not self._abstract_init):
            from deepspeed_tpu.telemetry.slo import SloMonitor
            self._slo = SloMonitor.from_config(
                tcfg, output_path=tcfg.output_path or "telemetry/",
                job_name=tcfg.job_name or "",
                registry=self.telemetry.registry, ledger=self._goodput,
                on_escalate=(self.telemetry._force_trace_export
                             if tcfg.trace else None))
            if self._guardian is not None:
                self._slo.on_anomaly = self._guardian.hook("slo")

        # ---- live observability plane (telemetry/obs_server.py) -----------
        # The HTTP scrape/status endpoint, rank-0 with the manager.
        # Providers are MONITOR-LEVEL report() bound methods — each
        # serves its latest HOST-SIDE snapshot; never the engine's
        # *_report wrappers, which force a device tick first. A scrape
        # must never force a device fetch, sync, or compile.
        self._obs_server = None
        if (self.telemetry.enabled
                and bool(getattr(tcfg, "server_enabled", False))
                and not self._abstract_init):
            from deepspeed_tpu.telemetry import incidents as _inc_mod
            from deepspeed_tpu.telemetry import obs_server as _obs_mod
            srv = _obs_mod.ObsServer.from_config(
                tcfg, registry=self.telemetry.registry,
                # rank identity rides every /metrics sample as a const
                # label so a federation aggregator's merged view stays
                # attributable without rewriting scraped text
                identity=({"rank": str(dist.get_rank())}
                          if bool(getattr(tcfg, "federation_enabled",
                                          False)) else None))
            if self.telemetry.health is not None:
                srv.register("health", self.telemetry.health.report)
            if self._goodput is not None:
                led = self._goodput
                srv.register(
                    "goodput", led.report,
                    age_s_fn=lambda: (
                        round(led.elapsed()
                              - (led.last_window["start_s"]
                                 + led.last_window["dur_s"]), 3)
                        if led.last_window else None))
            if self._memory is not None:
                srv.register("memory", self._memory.report)
            if self._fleet_monitor is not None:
                srv.register("fleet", self._fleet_monitor.report,
                             age_s_fn=self._fleet_monitor.last_poll_age_s)
            if self._guardian is not None:
                srv.register("guardian", self._guardian.report)
            if self._chronicle is not None:
                chron = self._chronicle
                srv.register("chronicle", chron.report)
                srv.register(
                    "incidents",
                    lambda: _inc_mod.correlate(
                        chron.snapshot_events(),
                        step_window=getattr(tcfg, "chronicle_step_window",
                                            8),
                        time_window_us=int(
                            getattr(tcfg, "chronicle_time_window_s", 30.0)
                            * 1e6),
                        job_name=tcfg.job_name or ""))
            if self._slo is not None:
                srv.register("slo", self._slo.report,
                             age_s_fn=self._slo.last_eval_age_s)
            self._obs_server = srv
            _obs_mod.set_obs_server(srv)
            log_dist(f"telemetry: obs server live at {srv.url} "
                     f"({len(srv.providers())} provider(s))", ranks=[0])

        # ---- fleet federation (telemetry/federation.py) -------------------
        # Cross-process mission control. EVERY rank with a live plane
        # announces its endpoint into the run-dir peer registry; the
        # aggregator rank (policy: auto -> rank 0) additionally scrapes
        # the whole fleet and serves the merged views off its own obs
        # server (/federation/*, /api/fleet/*). Scraping is host-side
        # HTTP only — zero device work, zero extra compiles on any rank.
        self._fleet_aggregator = None
        if (self._obs_server is not None
                and bool(getattr(tcfg, "federation_enabled", False))):
            fed_run_dir = getattr(tcfg, "federation_run_dir", "") or (
                self._chronicle.run_dir if self._chronicle is not None
                else os.path.join(tcfg.output_path or "telemetry/",
                                  "chronicle"))
            self._obs_server.announce(
                fed_run_dir, rank=dist.get_rank(),
                job_name=tcfg.job_name or "")
            policy = str(getattr(tcfg, "federation_aggregator", "auto"))
            arm_agg = (policy == "always"
                       or (policy == "auto" and dist.get_rank() == 0))
            if arm_agg:
                from deepspeed_tpu.telemetry import federation as _fed_mod
                try:
                    self._fleet_aggregator = \
                        _fed_mod.FleetAggregator.from_config(
                            tcfg,
                            output_path=tcfg.output_path or "telemetry/",
                            run_dir=fed_run_dir,
                            job_name=tcfg.job_name or "")
                    self._fleet_aggregator.attach(self._obs_server)
                    log_dist(
                        "telemetry: fleet aggregator armed "
                        f"(run_dir={fed_run_dir}, "
                        f"{len(self._fleet_aggregator.peers())} peer(s) "
                        "at start)", ranks=[0])
                except Exception as e:
                    # federation is an observer of the fleet, never a
                    # reason a rank fails to come up
                    logger.warning(
                        "[federation] aggregator arming failed: %s", e)
                    self._fleet_aggregator = None

        # ---- parameters / state init --------------------------------------
        with self.telemetry.span("engine/init_state"):
            self._init_state(model_parameters, sample_batch)
        if self.telemetry.compile_watch is not None \
                and not self._abstract_init:
            # retrace reports name the engine's program, not a lambda; the
            # jitted originals stay reachable via _compile_watch_target
            # (lower_train_step unwraps for the AOT .lower surface)
            self._jit_micro = self.telemetry.wrap_compiled(
                self._jit_micro, "micro_step")
            self._jit_train = self.telemetry.wrap_compiled(
                self._jit_train, "fused_train_step")
            self._jit_apply = self.telemetry.wrap_compiled(
                self._jit_apply, "apply_step")
            self._jit_offload_pre = self.telemetry.wrap_compiled(
                self._jit_offload_pre, "offload_pre_step")
            self._jit_eval = self.telemetry.wrap_compiled(
                self._jit_eval, "eval_step")

        # ---- async input pipeline (runtime/prefetch.py) -------------------
        # deepspeed_io wraps its loaders; train_batch wraps user-supplied
        # iterators (cached by identity so the pipeline is built once).
        # close() tears every pipeline down; each also self-registers an
        # atexit close as the leak backstop.
        self._prefetch_cfg = self.config.data_prefetch
        self._prefetchers = []
        self._prefetch_wrap_cache = {}
        self._warned_io_workers = False
        self._warned_prefetch_host_only = False
        self._warned_prefetch_stateful = False

        # ---- async checkpointing (runtime/async_checkpoint.py) ------------
        # snapshot-then-persist: save_checkpoint returns after the
        # device->host snapshot; a background writer persists while
        # training continues. Writer built lazily on the first async save.
        self._ckpt_async = bool(getattr(self.config,
                                        "checkpoint_async_save", False))
        self._ckpt_writer = None

        # ---- dataloader (reference deepspeed_io, :1474) -------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # ---- monitor (reference tensorboard wiring, engine.py:510) --------
        from deepspeed_tpu.monitor.monitor import MonitorMaster
        import deepspeed_tpu.comm as _dist
        self.monitor = MonitorMaster(
            self.config.tensorboard, rank=_dist.get_rank(),
            telemetry_config=self.config.telemetry,
            metrics_registry=self.telemetry.registry)

        # ---- flops profiler (reference engine.py:1722 step trigger) -------
        self.flops_profiler = None
        if self.config.flops_profiler_config.enabled:
            from deepspeed_tpu.profiling.flops_profiler.profiler import \
                FlopsProfiler
            self.flops_profiler = FlopsProfiler(ds_engine=self)

        # ---- timers -------------------------------------------------------
        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu() * self.dp_world_size,
            steps_per_output=self.steps_per_print())
        self._breakdown_steps = 0  # global steps since the last breakdown log
        if self._goodput is not None:
            # the goodput report's wall_clock_breakdown section reads the
            # SAME recorded timer intervals the breakdown log prints, so
            # the two reports cannot disagree (satellite: one step loop,
            # one timing system)
            self._goodput.breakdown_fn = self._breakdown_summary
        if self.wall_clock_breakdown():
            log_dist(
                "wall_clock_breakdown: XLA fuses forward+backward into one "
                "vjp program; the 'forward' timer carries the fused fwd+bwd "
                "time ('backward' is host bookkeeping only)", ranks=[0])

        log_dist(
            f"DeepSpeedEngine ready: zero_stage={self.zero_stage} "
            f"dtype={self.compute_dtype.__name__} dp={self.dp_world_size} "
            f"mp={self.mp_world_size} gas={self.gradient_accumulation_steps()}",
            ranks=[0])
        if self.config.dump_state:  # reference engine.py:245 dump_state
            self.config.print("DeepSpeedEngine configuration")
        self._chronicle_emit(
            "init",
            detail=f"zero_stage={self.zero_stage} "
                   f"dtype={self.compute_dtype.__name__} "
                   f"dp={self.dp_world_size} mp={self.mp_world_size} "
                   f"gas={self.gradient_accumulation_steps()}")

    # ------------------------------------------------------------------ config
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def steps_per_print(self):
        return self.config.steps_per_print

    def wall_clock_breakdown(self):
        """Reference API (engine.py:585). When enabled the gas=1 fused
        program is split back into micro+apply so the phases are separately
        timeable — same trade the reference makes with its cuda syncs."""
        return self.config.wall_clock_breakdown

    def zero_optimization_stage(self):
        return self.zero_stage

    def fp16_enabled(self):
        return self.config.fp16_enabled

    def bfloat16_enabled(self):
        return self.config.bfloat16_enabled

    def gradient_clipping(self):
        return self.config.gradient_clipping

    @property
    def loss_scale(self):
        return float(jax.device_get(self.state.scale.loss_scale))

    def get_lr(self):
        """Current lr — the value the NEXT applied step will use. Indexed by
        successful steps (state.step), matching the scheduler's counter."""
        applied_steps = self.global_steps - self.skipped_steps
        return [float(self._lr_fn(max(0, applied_steps)))]

    def get_global_grad_norm(self):
        """Global grad norm as a host FLOAT (the reference's contract —
        engine.py:477 returns ``self._global_grad_norm``), cached at
        ``steps_per_print`` cadence where the log line already pays the
        device sync. ``None`` until the first cadence fetch, and always
        ``None`` when the step has no reason to compute the norm
        (bf16/fp32 with clipping disabled and ``telemetry.health`` off) —
        returning the live device array here used to hand callers a
        hidden per-call host<->device sync."""
        return self._last_grad_norm

    # --------------------------------------------------------------- optimizer
    def _validate_onebit_config(self, name):
        """The compressed 1-bit data path needs rank-local grads, which is
        incompatible with features that re-layout or pre-reduce them. The
        reference has the same envelope (1-bit Adam requires the plain
        FP16_Optimizer: no ZeRO, no MP — onebit/adam.py:14 docstring)."""
        bad = []
        if self.zero_stage != 0:
            bad.append(f"zero_optimization.stage={self.zero_stage} (need 0)")
        if self.mp_world_size != 1:
            bad.append(f"model parallel size {self.mp_world_size} (need 1)")
        if groups.get_expert_parallel_world_size() != 1:
            bad.append("expert parallelism (need ep=1)")
        if groups.get_pipe_parallel_world_size() != 1:
            bad.append("pipeline parallelism (need pp=1)")
        if self._offload:
            bad.append("optimizer offload")
        if self.config.gradient_clipping > 0:
            bad.append("gradient_clipping (global norm needs an exact "
                       "grad allreduce, defeating the compression)")
        if self._batch_spec is not None:
            bad.append("custom batch_spec (sequence parallelism)")
        if bad:
            raise ValueError(
                f"{name} with the compressed collective (dp="
                f"{self.dp_world_size}) is incompatible with: "
                + "; ".join(bad))

    def _configure_optimizer(self):
        if self.client_optimizer is not None:
            assert isinstance(self.client_optimizer, optim_lib.Optimizer), (
                "client optimizer must be a deepspeed_tpu Optimizer(init, update) pair")
            return self.client_optimizer

        name = self.config.optimizer_name or ADAM_OPTIMIZER
        params = dict(self.config.optimizer_params or {})
        params.pop("lr", None)
        betas = params.pop("betas", (0.9, 0.999))
        torch_adam = params.pop("torch_adam", False)
        params.pop("max_grad_norm", None)
        # "fused": use the Pallas kernel path (ops/adam, ops/lamb) instead
        # of the XLA-fused jnp update; both are bit-compatible.
        use_fused = params.pop("fused", False)
        # "sweep": the whole-state flattened one-pass Adam (clip + update
        # [+ cast] fused over contiguous state — ops/adam fused_adam_sweep)
        use_sweep = params.pop("sweep", False)
        if use_sweep and name not in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER):
            raise ValueError(
                f"optimizer.params.sweep is the whole-state fused-Adam "
                f"path; it does not apply to optimizer {name!r}")

        if name == ONEBIT_ADAM_OPTIMIZER:
            kw = dict(
                b1=betas[0], b2=betas[1], eps=params.get("eps", 1e-8),
                weight_decay=params.get("weight_decay", 0.0),
                freeze_step=params.get("freeze_step", 100),
                adam_w_mode=params.pop("adam_w_mode", True),
                bias_correction=params.get("bias_correction", True))
            if self.dp_world_size > 1:
                # the point of 1-bit Adam is changed WIRE traffic: grads
                # stay rank-local and the momenta travel through the
                # compressed collective (reference onebit/adam.py:14 +
                # comm/nccl.py:47) — see _build_onebit_step_fns
                self._validate_onebit_config(name)
                from deepspeed_tpu.runtime.fp16.onebit.adam import \
                    onebit_adam_engine
                self._onebit_dist = True
                return onebit_adam_engine(
                    groups.DATA_AXIS, self.dp_world_size, **kw)
            from deepspeed_tpu.runtime.fp16.onebit.adam import onebit_adam
            return onebit_adam(**kw)
        if name == ONEBIT_LAMB_OPTIMIZER:
            kw = dict(
                b1=betas[0], b2=betas[1], eps=params.get("eps", 1e-6),
                weight_decay=params.get("weight_decay", 0.0),
                freeze_step=params.get("freeze_step", 100),
                min_coeff=params.get("min_coeff", 0.01),
                max_coeff=params.get("max_coeff", 10.0))
            if self.dp_world_size > 1:
                self._validate_onebit_config(name)
                from deepspeed_tpu.runtime.fp16.onebit.lamb import \
                    onebit_lamb_engine
                self._onebit_dist = True
                return onebit_lamb_engine(
                    groups.DATA_AXIS, self.dp_world_size, **kw)
            from deepspeed_tpu.runtime.fp16.onebit.lamb import onebit_lamb
            return onebit_lamb(**kw)
        if name in (ADAM_OPTIMIZER, ADAMW_OPTIMIZER):
            # Reference: both "adam" and "adamw" route to FusedAdam, which
            # defaults to adam_w_mode=True (ops/adam/fused_adam.py:16).
            adam_w_mode = params.pop("adam_w_mode", True)
            del torch_adam
            kw = dict(b1=betas[0], b2=betas[1],
                      eps=params.get("eps", 1e-8),
                      weight_decay=params.get("weight_decay", 0.0),
                      adam_w_mode=adam_w_mode,
                      bias_correction=params.get("bias_correction", True))
            if use_sweep:
                from deepspeed_tpu.ops.adam.fused_adam import \
                    fused_adam_sweep
                return fused_adam_sweep(**kw)
            if use_fused:
                from deepspeed_tpu.ops.adam.fused_adam import fused_adam
                return fused_adam(**kw)
            return optim_lib.adam(**kw)
        if name == LAMB_OPTIMIZER:
            kw = dict(b1=betas[0], b2=betas[1],
                      eps=params.get("eps", 1e-6),
                      weight_decay=params.get("weight_decay", 0.0),
                      min_coeff=params.get("min_coeff", 0.01),
                      max_coeff=params.get("max_coeff", 10.0),
                      bias_correction=params.get("bias_correction", True))
            if use_fused:
                from deepspeed_tpu.ops.lamb.fused_lamb import fused_lamb
                return fused_lamb(**kw)
            return optim_lib.lamb(**kw)
        if name == SGD_OPTIMIZER:
            return optim_lib.sgd(momentum=params.get("momentum", 0.0),
                                 weight_decay=params.get("weight_decay", 0.0),
                                 nesterov=params.get("nesterov", False))
        if name == ADAGRAD_OPTIMIZER:
            return optim_lib.adagrad(eps=params.get("eps", 1e-8),
                                     weight_decay=params.get("weight_decay", 0.0))
        raise ValueError(f"Unsupported optimizer: {name}")

    def _configure_lr_scheduler(self):
        base_lr = float((self.config.optimizer_params or {}).get("lr", 1e-3))
        if self.client_lr_scheduler is not None:
            sched = self.client_lr_scheduler
            return sched, sched.as_schedule_fn(), base_lr
        if self.config.scheduler_name is not None:
            sched = get_lr_schedule(self.config.scheduler_name,
                                    self.config.scheduler_params)
            return sched, sched.as_schedule_fn(), base_lr
        return None, (lambda step: base_lr), base_lr

    # ------------------------------------------------------------------- state

    def _make_offload_optimizer(self):
        from deepspeed_tpu.runtime.zero.offload import OffloadedOptimizer
        op = dict(self.config.optimizer_params or {})
        nvme_path = None
        if self._offload_device == "nvme":
            nvme_path = (self.config.zero_config.offload_optimizer
                         .nvme_path or "/tmp")
        return OffloadedOptimizer(
            self.state.params, lr=self._base_lr,
            betas=op.get("betas", (0.9, 0.999)),
            eps=op.get("eps", 1e-8),
            weight_decay=op.get("weight_decay", 0.0),
            adam_w_mode=op.get("adam_w_mode", True),
            nvme_path=nvme_path)

    def _init_state(self, model_parameters, sample_batch):
        if self._abstract_init:
            assert sample_batch is not None, (
                "abstract_init needs sample_batch for shape inference")
            assert model_parameters is None, (
                "abstract_init derives shapes from module.init and would "
                "silently ignore model_parameters — pass one or the other")
            assert not (self._offload or self._onebit_dist
                        or self._sparse_grads), (
                "abstract_init supports the monolithic (non-offload, "
                "non-1-bit, dense-grad) engine paths")
            rng = jax.random.PRNGKey(self._seed)
            params = jax.eval_shape(self.module.init, rng, sample_batch)
            if isinstance(params, dict) and set(params.keys()) == {"params"}:
                params = params["params"]
            params = jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(
                    s.shape,
                    jnp.float32 if jnp.issubdtype(s.dtype, jnp.floating)
                    else s.dtype), params)
        elif model_parameters is not None:
            params = _cast_tree(model_parameters, jnp.float32)
        else:
            assert sample_batch is not None, (
                "need model_parameters or sample_batch to initialise the model")
            rng = jax.random.PRNGKey(self._seed)
            # jit, not eager: only the param outputs are live, so jaxpr
            # DCE drops the whole traced forward — init neither executes
            # the model nor lowers its kernels (an eager fp32 init
            # forward VMEM-OOMed the flash kernel at seq 8192)
            params = jax.jit(self.module.init)(rng, sample_batch)
            if isinstance(params, dict) and set(params.keys()) == {"params"}:
                params = params["params"]
            # fp32 master copy (reference FP16_Optimizer master weights)
            params = _cast_tree(params, jnp.float32)

        min_numel = self.config.zero_config.param_persistence_threshold
        self.param_shardings = build_param_shardings(
            params, self.mesh, self.zero_stage, self.mp_rules,
            min_shard_numel=min_numel)

        # persistence threshold only gates stage-3 param sharding (the
        # ds_persist analogue); optimizer/grad shards have no fetch cost so
        # they always shard when divisible.
        if self._offload:
            # optimizer state lives host-side: nothing on the device
            opt_shape = ()
        else:
            opt_shape = jax.eval_shape(self.optimizer.init, params)
        if self._onebit_dist:
            # mu/nu are synchronized by the collective (replicated); the
            # error-feedback buffers are RANK-LOCAL, laid out flat with
            # the rank dim folded in and sharded over the data axis (see
            # onebit_adam_engine); accumulated grads are rank-local too,
            # stored with a leading [dp] dim.
            repl = NamedSharding(self.mesh, P())
            ranked = NamedSharding(self.mesh, P(groups.DATA_AXIS))
            self.opt_shardings = type(opt_shape)(
                step=repl,
                mu=jax.tree.map(lambda _: repl, opt_shape.mu),
                nu=jax.tree.map(lambda _: repl, opt_shape.nu),
                worker_error=jax.tree.map(lambda _: ranked,
                                          opt_shape.worker_error),
                server_error=jax.tree.map(lambda _: ranked,
                                          opt_shape.server_error))
            self.grad_shardings = jax.tree.map(
                lambda p: NamedSharding(
                    self.mesh, P(groups.DATA_AXIS, *([None] * p.ndim))),
                params)
            self._grad_constraint = lambda g: g
        else:
            self.opt_shardings = build_opt_shardings(
                opt_shape, self.mesh, self.zero_stage, self.mp_rules,
                min_shard_numel=0)

            # grads accumulate with the stage>=2 layout (reduce-scattered);
            # stage<2 keeps them like the params (replicated across DP).
            self.grad_shardings = build_opt_shardings(
                jax.eval_shape(lambda p: p, params), self.mesh,
                1 if self.zero_stage >= 2 else 0, self.mp_rules,
                min_shard_numel=0)
            self._grad_constraint = grad_constraint_fn(
                self.mesh, self.zero_stage, self.mp_rules, min_shard_numel=0)

        scalar_sh = NamedSharding(self.mesh, P())
        self.state_shardings = TrainState(
            step=scalar_sh,
            params=self.param_shardings,
            opt_state=self.opt_shardings,
            acc_grads=self.grad_shardings,
            scale=LossScaleState(loss_scale=scalar_sh, good_steps=scalar_sh,
                                 hysteresis=scalar_sh))

        # Build the initial state ON the mesh with one compiled init fn so
        # every leaf is born sharded (no host round-trip of full params).
        dp = self.dp_world_size

        # gradient_accumulation_dtype (reference "data_types" block):
        # fp32 default; bf16/fp16 halve the accumulator's HBM footprint at
        # the cost of accumulation precision. The 1-bit path keeps fp32 —
        # its error-feedback residuals are precision-critical.
        acc_dtype = {None: jnp.float32, "fp32": jnp.float32,
                     "bf16": jnp.bfloat16, "fp16": jnp.float16}[
                         self.config.gradient_accumulation_dtype]

        def make_acc(x):
            if self._onebit_dist:   # rank-local accumulation: [dp, ...]
                return jnp.zeros((dp,) + x.shape, jnp.float32)
            return jnp.zeros_like(x, acc_dtype)

        def make_state(p):
            return TrainState(
                step=jnp.zeros([], jnp.int32),
                params=p,
                opt_state=() if self._offload else self.optimizer.init(p),
                acc_grads=jax.tree.map(make_acc, p),
                scale=make_scale_state(
                    self._init_scale,
                    delayed_shift=self.config.fp16.hysteresis))

        if self._abstract_init:
            # no materialisation: the state is a ShapeDtypeStruct tree the
            # step fns lower against (lower_train_step)
            self.state = jax.eval_shape(make_state, params)
        else:
            with self.mesh:
                params = jax.device_put(params, self.param_shardings)
                self.state = jax.jit(
                    make_state, out_shardings=self.state_shardings)(params)

        if self._offload:
            self._offload_opt = self._make_offload_optimizer()

        if self._sparse_grads:
            self._sparse_mask = self._build_sparse_mask(params)
            if not any(self._sparse_mask):
                logger.warning(
                    "sparse_gradients enabled but no parameter matched "
                    f"{self._sparse_grad_rules}; falling back to dense")
                self._sparse_grads = False

        self._build_step_fns()
        self._pending_loss = None
        self._last_grad_norm = None      # host FLOAT, cached at print cadence
        self._pending_grad_norm = None   # device scalar of the last step
        self._last_batch = None
        self._pending_health_stats = None  # device stats pytree (no sync)
        self._health_last_loss = None      # device scalar loss (no sync)
        self._health_last_obs_step = -1

    def _abstract_step_args(self, batch):
        """(batch_sharded, rng, theta) ShapeDtypeStructs for AOT-lowering
        a step program at this engine's shapes — ``batch`` may be arrays
        or ShapeDtypeStructs; only avals are read."""
        import numpy as _np
        batch_sds = jax.tree.map(
            lambda x: x if isinstance(x, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(_np.shape(x), _np.asarray(x).dtype),
            batch)
        rng_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
        theta_sds = jax.ShapeDtypeStruct((), jnp.float32)
        batch_sharded = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=sh),
            batch_sds, self._batch_sharding(batch_sds))
        return batch_sharded, rng_sds, theta_sds

    def lower_train_step(self, batch):
        """AOT-lower the fused global train step (gas=1) at the engine's
        shapes WITHOUT executing anything — the at-scale proof for
        configs (e.g. GPT-2 1.5B ZeRO-3 over 16 chips) that no single
        host could materialise. ``batch`` may be arrays or
        ShapeDtypeStructs. Returns the ``jax.stages.Lowered``; call
        ``.compile().memory_analysis()`` for the per-chip footprint."""
        assert self._abstract_init, (
            "lower_train_step is the abstract_init=True surface; a "
            "materialised engine can just run train_batch")
        assert self._jit_train is not None, (
            "lower_train_step needs the fused gas=1 step (gradient "
            "accumulation > 1 lowers per-microbatch programs instead)")
        with self.mesh:
            batch_sharded, rng_sds, theta_sds = \
                self._abstract_step_args(batch)
            # the compile-watch wrapper (if any) hides the AOT surface
            jit_train = getattr(self._jit_train, "_compile_watch_target",
                                self._jit_train)
            return jit_train.lower(self.state, batch_sharded,
                                   rng_sds, theta_sds)

    def lower_step_programs(self, batch):
        """AOT-lower every program one global step dispatches, WITHOUT
        executing anything: ``{"fused_train_step": Lowered}`` for the
        gas=1 fused config, ``{"micro_step": ..., "apply_step": ...}``
        for gradient accumulation (or wall_clock_breakdown) configs.
        ``batch`` is ONE dispatch's batch (micro_batch x dp samples —
        the same shape ``train_batch`` pulls from its iterator); arrays
        or ShapeDtypeStructs.

        This is the autotuner's stage-1 surface: compile each Lowered
        once, census/prune/rank the candidate, then hand the artifacts
        to a materialised twin engine via ``adopt_compiled_step`` so the
        measured probe compiles nothing."""
        assert self._abstract_init, (
            "lower_step_programs is the abstract_init=True surface; a "
            "materialised engine owns its programs via the cost explorer")
        with self.mesh:
            batch_sharded, rng_sds, theta_sds = \
                self._abstract_step_args(batch)
            out = {}
            if self._jit_train is not None:
                jit_train = getattr(self._jit_train,
                                    "_compile_watch_target",
                                    self._jit_train)
                out["fused_train_step"] = jit_train.lower(
                    self.state, batch_sharded, rng_sds, theta_sds)
            else:
                jit_micro = getattr(self._jit_micro,
                                    "_compile_watch_target",
                                    self._jit_micro)
                out["micro_step"] = jit_micro.lower(
                    self.state, batch_sharded, rng_sds, theta_sds)
                if self._jit_apply is not None and not self._offload:
                    jit_apply = getattr(self._jit_apply,
                                        "_compile_watch_target",
                                        self._jit_apply)
                    out["apply_step"] = jit_apply.lower(self.state)
            return out

    def _build_sparse_mask(self, params):
        """Flat boolean mask over the param leaves: True = embedding table
        whose grad travels the sparse path (name matches
        sparse_embedding_rules and it is a >=2-D table)."""
        import re
        from deepspeed_tpu.runtime.zero.partition import _path_str
        pats = [re.compile(p) for p in self._sparse_grad_rules]
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        return [leaf.ndim >= 2 and
                any(p.search(_path_str(path)) for p in pats)
                for path, leaf in flat]

    # -------------------------------------------------------- compiled steps
    def _batch_sharding(self, batch):
        if self._batch_spec is not None:
            return jax.tree.map(
                lambda _: NamedSharding(self.mesh, self._batch_spec), batch)
        dp_axes = tuple(a for a in groups.data_parallel_axes()
                        if self.mesh.shape[a] > 1)
        spec = P(dp_axes) if dp_axes else P()
        return jax.tree.map(
            lambda _: NamedSharding(self.mesh, spec), batch)

    def _compute_loss(self, params, batch, rng, pld_theta=None):
        """Forward in compute dtype; returns scalar fp32 loss."""
        cparams = _cast_tree(params, self.compute_dtype)
        model_kwargs = {}
        if rng is not None:
            # "gating" feeds MoE RTS/noisy gating (moe/sharded_moe.py
            # TopKGate); unused rng names are ignored by flax
            model_kwargs["rngs"] = {"dropout": rng,
                                    "gating": jax.random.fold_in(rng, 7)}
        if self.progressive_layer_drop is not None and pld_theta is not None:
            # reference engine.forward kwarg injection (engine.py:1571)
            model_kwargs["progressive_layer_drop"] = True
            model_kwargs["pld_theta"] = pld_theta
        if hasattr(self.module, "apply"):
            out = self.module.apply(
                {"params": cparams} if not (isinstance(cparams, dict)
                                            and "params" in cparams) else cparams,
                batch, **model_kwargs)
        else:
            out = self.module(cparams, batch)
        loss = self.loss_fn(out, batch) if self.loss_fn is not None else out
        return jnp.asarray(loss, jnp.float32)

    def _make_sparse_vg(self):
        """(params, batch, rng, theta, scale) -> (scaled_loss, grads) with
        EXPLICIT DP reduction under shard_map: dense grads pmean over the
        data axis, embedding-table grads as an all-gather of the batch's
        (token-id, row) pairs + scatter-add — the reference
        ``sparse_allreduce_bucket`` dataflow (engine.py:2196-2268). Wire
        cost per table: dp*k*(D+1) elements instead of dp*V*D."""
        import functools

        from deepspeed_tpu.runtime.sparse_tensor import sparse_all_reduce
        from deepspeed_tpu.utils.jax_compat import get_shard_map
        shard_map, smap_kw = get_shard_map()
        axis = groups.DATA_AXIS
        mask = self._sparse_mask
        ids_fn = self._sparse_ids_fn

        def body(params, batch, rng, theta, scale):
            rrng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

            def scaled_loss(p):
                loss = self._compute_loss(p, batch, rrng, theta)
                return loss * scale

            sloss, grads = jax.value_and_grad(scaled_loss)(params)
            ids = jnp.asarray(ids_fn(batch), jnp.int32).reshape(-1)
            # dedup once (table-independent) so the row gather +
            # scatter-add doesn't double count repeated tokens; padding
            # slots get an out-of-range index (dropped by the scatter)
            # and zeroed values
            pad = jnp.iinfo(jnp.int32).max
            uniq = jnp.unique(ids, size=ids.size, fill_value=pad)
            flat, tdef = jax.tree_util.tree_flatten(grads)
            out = []
            for g, is_emb in zip(flat, mask):
                if is_emb:
                    vocab = g.shape[0]
                    uids = jnp.where(uniq == pad, vocab, uniq)
                    valid = uids < vocab
                    vals = jnp.take(g, jnp.where(valid, uids, 0), axis=0)
                    vals = vals * valid.reshape(
                        (-1,) + (1,) * (g.ndim - 1)).astype(g.dtype)
                    out.append(sparse_all_reduce(uids, vals, g.shape, axis,
                                                 op="mean"))
                else:
                    out.append(jax.lax.pmean(g, axis))
            return (jax.lax.pmean(sloss, axis),
                    jax.tree_util.tree_unflatten(tdef, out))

        smap = functools.partial(shard_map, mesh=self.mesh)
        return smap(body, in_specs=(P(), P(axis), P(), P(), P()),
                    out_specs=(P(), P()), **smap_kw)

    def _resolve_comm_overlap(self):
        """Arm the bucketed-reduction variant when the config asks for it
        AND the engine is inside the supported envelope. Outside it the
        engine falls back to the plain GSPMD reduction with ONE warning —
        comm_overlap is a perf knob, not a semantic switch, so a config
        that composes it with an unsupported feature should still train."""
        cfg = self._comm_overlap_cfg
        if not getattr(cfg, "enabled", False):
            return False
        # (_onebit_dist never reaches here: _build_step_fns routes that
        # case to _build_onebit_step_fns with its own warning first)
        bad = []
        if self.dp_world_size < 2:
            bad.append("data-parallel world size 1 (nothing to reduce)")
        if self._sparse_grads:
            bad.append("sparse_gradients (its shard_map owns the "
                       "reduction)")
        if self.zero_stage >= 2:
            bad.append(f"zero stage {self.zero_stage} (grads live "
                       "reduce-scattered; re-replicating them through a "
                       "bucketed psum would undo the partitioning)")
        if self.mp_world_size != 1:
            bad.append("model parallelism (params sharded over the "
                       "model axis; shard_map here maps the data axis "
                       "with replicated params)")
        if groups.get_expert_parallel_world_size() != 1:
            bad.append("expert parallelism")
        if groups.get_pipe_parallel_world_size() != 1:
            bad.append("pipeline parallelism")
        if self._batch_spec is not None:
            bad.append("custom batch_spec (the batch dim must shard "
                       "over the data axis)")
        if bad:
            if not self._warned_comm_overlap:
                self._warned_comm_overlap = True
                logger.warning(
                    "comm_overlap is enabled but falls back to the plain "
                    "GSPMD gradient reduction — incompatible with: "
                    + "; ".join(bad))
            return False
        if getattr(cfg, "scheduler_flags", True):
            from deepspeed_tpu.runtime.comm_overlap import \
                log_scheduler_flags_hint
            log_scheduler_flags_hint(jax.default_backend())
        return True

    def _make_overlap_vg(self):
        """(params, batch, rng, theta, scale) -> (scaled_loss, grads) with
        EXPLICIT bucketed DP reduction under shard_map: each rank computes
        grads from its own batch shard and every size-targeted bucket is
        mean-reduced by ONE psum, issued as soon as the backward has
        produced that bucket's grads (reverse-layer bucket order —
        runtime/comm_overlap.py). Arithmetically identical to the GSPMD
        per-leaf pmean; structurally B collectives instead of one per
        leaf, which is what the latency-hiding scheduler can overlap."""
        import functools

        from deepspeed_tpu.runtime.comm_overlap import bucketed_pmean
        from deepspeed_tpu.utils.jax_compat import get_shard_map
        shard_map, smap_kw = get_shard_map()
        axis = groups.DATA_AXIS
        spec = self._overlap_spec

        def body(params, batch, rng, theta, scale):
            rrng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

            def scaled_loss(p):
                loss = self._compute_loss(p, batch, rrng, theta)
                return loss * scale

            sloss, grads = jax.value_and_grad(scaled_loss)(params)
            grads = bucketed_pmean(spec, grads, axis)
            return jax.lax.pmean(sloss, axis), grads

        smap = functools.partial(shard_map, mesh=self.mesh)
        return smap(body, in_specs=(P(), P(axis), P(), P(), P()),
                    out_specs=(P(), P()), **smap_kw)

    def _build_step_fns(self):
        if self._onebit_dist:
            if getattr(self._comm_overlap_cfg, "enabled", False) \
                    and not self._warned_comm_overlap:
                self._warned_comm_overlap = True
                logger.warning(
                    "comm_overlap has no effect with the compressed 1-bit "
                    "optimizers (grads are rank-local by design); "
                    "disabled for this engine")
            self._build_onebit_step_fns()
            return
        gas = self.gradient_accumulation_steps()
        cfg = self.config

        # health stats variant: selected HERE, before the first lower, so
        # the _AOTStep artifact and the compile watch always see one fixed
        # step signature (never mutated mid-run). The offloaded optimizer
        # applies its update host-side, so the on-device epilogue cannot
        # see the update norm — degrade gracefully (log once, no stats).
        if self._health_on and self._offload:
            logger.warning(
                "[health] in-step stats are not supported with the "
                "offloaded optimizer step (the update runs host-side); "
                "disabling telemetry.health stats for this engine")
            self._health_on = False
        health = self._health_on
        if health:
            from deepspeed_tpu.telemetry.health import (build_bucket_spec,
                                                        bucket_grad_stats)
            self._health_spec = build_bucket_spec(
                self.state.params,
                depth=int(getattr(cfg.telemetry, "health_bucket_depth", 8)))
            self._wire_health_monitor()
            hspec = self._health_spec

        self._comm_overlap_on = self._resolve_comm_overlap()
        if self._comm_overlap_on:
            from deepspeed_tpu.runtime.comm_overlap import \
                build_grad_bucket_spec
            self._overlap_spec = build_grad_bucket_spec(
                self.state.params, self._comm_overlap_cfg.bucket_bytes)
            log_dist(
                f"comm_overlap: {self._overlap_spec.n_leaves} grad "
                f"leaves -> {self._overlap_spec.n_buckets} reduction "
                f"buckets (target "
                f"{self._comm_overlap_cfg.bucket_mb:g} MiB)", ranks=[0])
            if self.telemetry.enabled:
                self.telemetry.registry.gauge(
                    "comm_overlap_buckets",
                    "gradient reduction buckets per step").set(
                        self._overlap_spec.n_buckets)

        if self._fleet is not None and \
                getattr(cfg.telemetry, "fleet_desync", True):
            self._desync_on = self._resolve_desync()
            if self._desync_on:
                from deepspeed_tpu.telemetry.fleet import (
                    build_desync_checksum_fn, build_desync_spec)
                self._desync_spec = build_desync_spec(
                    self.state.params,
                    depth=int(getattr(cfg.telemetry, "health_bucket_depth",
                                      8)))
                self._desync_fn = build_desync_checksum_fn(
                    self.mesh, self._desync_spec, groups.DATA_AXIS)

        if self._sparse_grads:
            value_and_grad = self._make_sparse_vg()
        elif self._comm_overlap_on:
            value_and_grad = self._make_overlap_vg()
        else:
            def value_and_grad(params, batch, rng, theta, scale):
                def scaled_loss(p):
                    loss = self._compute_loss(p, batch, rng, theta)
                    return loss * scale
                return jax.value_and_grad(scaled_loss)(params)

        def micro_step(state, batch, rng, pld_theta):
            sloss, grads = value_and_grad(
                state.params, batch, rng, pld_theta,
                state.scale.loss_scale / gas)
            grads = self._grad_constraint(grads)
            # cast INTO the accumulator dtype (gradient_accumulation_dtype);
            # bare jnp.add would promote and silently widen the buffer
            acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                               state.acc_grads, grads)
            loss = sloss * gas / state.scale.loss_scale
            return state._replace(acc_grads=acc), loss

        # grad_norm is only needed on-device for clipping and for the fp16
        # overflow bookkeeping; in the bf16/fp32 no-clip case computing it
        # costs a full extra read of the grad tree per step, so it is
        # skipped and get_global_grad_norm() returns None. The health
        # observatory needs it as a stat, so health forces it on.
        need_norm = bool(cfg.fp16_enabled or cfg.gradient_clipping > 0
                         or health)
        self._need_norm = need_norm
        # whole-state sweep optimizer: the global-norm clip rides INSIDE
        # its one fused pass (update(clip_coef=...)), so the epilogue must
        # not also scale the grad tree — a separate full read+write of it.
        # The offloaded step applies its update host-side and never sees
        # clip_coef, so there the epilogue clip stays.
        fuse_clip = (bool(getattr(self.optimizer, "fuses_clip", False))
                     and not self._offload)

        def grad_epilogue(state, grads):
            """Shared end-of-accumulation math on an UNSCALED-pending grad
            tree: unscale, overflow check, norm + clip, scale-state update.
            Returns (state-with-new-scale, grads, grad_norm, finite,
            clip_coef, aux); ``aux`` holds the health bucket stats (empty
            dict when off) — computed on the unscaled PRE-clip grads, so a
            clip cannot mask an explosion and the provenance bitmask sees
            the raw values. ``clip_coef`` is the torch-semantics global
            clip coefficient (1.0 when clipping is off); a clip-fusing
            sweep optimizer consumes it instead of the tree-map below."""
            inv_scale = 1.0 / state.scale.loss_scale
            grads = jax.tree.map(lambda g: g * inv_scale, grads)
            finite = jnp.array(True)
            if cfg.fp16_enabled:
                finite = jnp.all(jnp.stack(
                    [jnp.isfinite(g).all() for g in jax.tree.leaves(grads)]))
            grad_norm = (optim_lib.global_norm(grads) if need_norm
                         else jnp.float32(0.0))
            aux = {}
            if health:
                norms, mask = bucket_grad_stats(hspec, grads)
                aux = {"bucket_norms": norms, "nonfinite_mask": mask}
            clip_coef = jnp.float32(1.0)
            if cfg.gradient_clipping > 0:
                # same coefficient clip_by_global_norm computes (the norm
                # is the grad_norm above — XLA CSEs the reduction)
                clip_coef = jnp.minimum(
                    cfg.gradient_clipping / (grad_norm + 1e-6),
                    jnp.float32(1.0))
                if not fuse_clip:
                    grads = jax.tree.map(lambda g: g * clip_coef, grads)
            new_scale = update_scale(
                state.scale, ~finite,
                dynamic=self._dynamic_scale,
                scale_window=cfg.fp16.loss_scale_window,
                min_scale=cfg.fp16.min_loss_scale,
                delayed_shift=cfg.fp16.hysteresis)
            return (state._replace(scale=new_scale), grads, grad_norm,
                    finite, clip_coef, aux)

        def grad_prologue(state):
            """grad_epilogue over the accumulation buffer, which it resets."""
            acc = jax.tree.map(lambda a: a.astype(jnp.float32),
                               state.acc_grads)
            zeros = jax.tree.map(jnp.zeros_like, state.acc_grads)
            return grad_epilogue(state._replace(acc_grads=zeros), acc)

        def optimizer_update(state, grads, finite, clip_coef):
            """Returns (state, update_norm); the norm is a constant 0 when
            health is off (dead output, DCE'd by XLA). ``clip_coef`` only
            reaches a clip-fusing sweep optimizer — everyone else already
            received clipped grads from the epilogue."""
            lr = self._lr_fn_traced(state.step)

            def do_update(operand):
                st, g, cc = operand
                if fuse_clip:
                    updates, new_opt = self.optimizer.update(
                        g, st.opt_state, st.params, lr, clip_coef=cc)
                else:
                    updates, new_opt = self.optimizer.update(
                        g, st.opt_state, st.params, lr)
                new_params = jax.tree.map(jnp.add, st.params, updates)
                un = (optim_lib.global_norm(updates) if health
                      else jnp.float32(0.0))
                return st._replace(step=st.step + 1, params=new_params,
                                   opt_state=new_opt), un

            def skip_update(operand):
                st, _, _ = operand
                return st, jnp.float32(0.0)

            return jax.lax.cond(finite, do_update, skip_update,
                                (state, grads, clip_coef))

        def pack_stats(state, grad_norm, finite, upd_norm, aux):
            """The static-shaped in-step stats pytree (health only). The
            update ratio uses the APPLIED update (optimizer output, lr
            already inside) against the post-update params; a skipped step
            reports 0. loss_scale/good_steps/hysteresis come from the
            POST-update scale state, so the host sees the machine as the
            NEXT step will."""
            pnorm = optim_lib.global_norm(state.params)
            return {
                "grad_norm": grad_norm,
                "param_norm": pnorm,
                "update_ratio": jnp.where(pnorm > 0, upd_norm / pnorm,
                                          jnp.float32(0.0)),
                "bucket_grad_norms": aux["bucket_norms"],
                "nonfinite_buckets": aux["nonfinite_mask"],
                "overflow": ~finite,
                **scale_state_stats(state.scale),
            }

        def apply_step(state):
            (state, grads, grad_norm, finite, clip_coef,
             aux) = grad_prologue(state)
            state, upd_norm = optimizer_update(state, grads, finite,
                                               clip_coef)
            if health:
                return (state, grad_norm, ~finite,
                        pack_stats(state, grad_norm, finite, upd_norm, aux))
            return state, grad_norm, ~finite

        def fused_train_step(state, batch, rng, pld_theta):
            """gas=1 fast path: forward+backward+optimizer in ONE compiled
            program. Skipping the acc_grads round-trip (write grads, read
            them back, write zeros) saves ~3x the grad-tree bytes of HBM
            traffic per step; acc_grads passes through untouched (it is
            all-zeros between steps by invariant, and the donated buffer
            aliases through at zero cost)."""
            sloss, grads = value_and_grad(
                state.params, batch, rng, pld_theta,
                state.scale.loss_scale)
            grads = self._grad_constraint(grads)
            loss = sloss / state.scale.loss_scale
            (state, grads, grad_norm, finite, clip_coef,
             aux) = grad_epilogue(state, grads)
            state, upd_norm = optimizer_update(state, grads, finite,
                                               clip_coef)
            if health:
                return (state, loss, grad_norm, ~finite,
                        pack_stats(state, grad_norm, finite, upd_norm, aux))
            return state, loss, grad_norm, ~finite

        def offload_pre_step(state):
            """Device half of the offloaded step: the shared prologue —
            grads go to the host CPU-Adam; params unchanged. fuse_clip is
            forced off under offload, so the grads here are clipped."""
            state, grads, grad_norm, finite, _, _ = grad_prologue(state)
            return state, grads, grad_norm, ~finite

        sh = self.state_shardings
        scalar = NamedSharding(self.mesh, P())
        # the stats pytree is all replicated scalars (+ one [B] bucket
        # vector); keys must match pack_stats exactly
        stats_sh = {k: scalar for k in (
            "grad_norm", "param_norm", "update_ratio", "bucket_grad_norms",
            "nonfinite_buckets", "overflow", "loss_scale", "good_steps",
            "hysteresis")}
        self._jit_micro = jax.jit(
            micro_step, donate_argnums=0,
            in_shardings=(sh, None, None, None),
            out_shardings=(sh, scalar))
        # gas=1 (the common large-model config): one fused program per
        # global step instead of micro+apply with an HBM acc round-trip
        self._jit_train = None
        if gas == 1 and not self._offload and not cfg.wall_clock_breakdown:
            self._jit_train = jax.jit(
                fused_train_step, donate_argnums=0,
                in_shardings=(sh, None, None, None),
                out_shardings=((sh, scalar, scalar, scalar, stats_sh)
                               if health else
                               (sh, scalar, scalar, scalar)))
        self._jit_offload_pre = jax.jit(
            offload_pre_step, donate_argnums=0,
            in_shardings=(sh,),
            out_shardings=(sh, self.grad_shardings, scalar, scalar))
        self._jit_apply = jax.jit(
            apply_step, donate_argnums=0,
            in_shardings=(sh,),
            out_shardings=((sh, scalar, scalar, stats_sh) if health else
                           (sh, scalar, scalar)))
        self._jit_eval = jax.jit(
            lambda params, batch: self._compute_loss(params, batch, None))
        self._install_aot_steps()

    def _install_aot_steps(self):
        """Cost-explorer mode: own the step programs' compiled artifacts
        (see _AOTStep). The TRAIN entry points only — eval/offload
        auxiliaries are not the program being explained. apply_step rides
        along (gas>1 dispatches it once per global step) so the autotuner
        can hand a gas>1 trial BOTH of its stage-1 artifacts and the probe
        compiles nothing; its census never overwrites the step census
        (_on_step_compiled filters by name)."""
        if not self._cost_explorer_on:
            return
        if self._jit_train is not None:
            self._jit_train = _AOTStep(self._jit_train, "fused_train_step",
                                       self._on_step_compiled)
        self._jit_micro = _AOTStep(self._jit_micro, "micro_step",
                                   self._on_step_compiled)
        if self._jit_apply is not None:
            self._jit_apply = _AOTStep(self._jit_apply, "apply_step",
                                       self._on_step_compiled)

    def _build_onebit_step_fns(self):
        """Step fns for the compressed 1-bit optimizers (reference
        onebit/adam.py:14 + comm/nccl.py:47 compressed_allreduce).

        The normal path lets XLA psum the grads over the data axis — exact
        fp32 reduction, which makes post-freeze "compression" a no-op on
        the wire. Here the whole micro/apply pair runs under ``shard_map``
        over the data axis: each rank computes grads from its OWN batch
        shard, accumulates them rank-locally ([dp, ...] acc layout), and
        the only cross-rank traffic is the optimizer's own collectives —
        an exact pmean during warmup, the sign-packed uint8 wire format
        (comm/compressed.py) after ``freeze_step``.
        """
        gas = self.gradient_accumulation_steps()
        cfg = self.config
        axis = groups.DATA_AXIS
        import functools

        if self._health_on:
            logger.warning(
                "[health] in-step stats are not supported with the "
                "compressed 1-bit optimizers (rank-local shard_map grads, "
                "no global epilogue); disabling telemetry.health stats "
                "for this engine")
            self._health_on = False

        from deepspeed_tpu.utils.jax_compat import get_shard_map
        shard_map, smap_kw = get_shard_map()
        smap = functools.partial(shard_map, mesh=self.mesh)

        opt_spec = type(self.state.opt_state)(
            step=P(), mu=P(), nu=P(),
            worker_error=P(axis), server_error=P(axis))

        def micro_step(state, batch, rng, pld_theta):
            def body(params, acc, scale, batch, rng, theta):
                rrng = jax.random.fold_in(rng, jax.lax.axis_index(axis))

                def scaled_loss(p):
                    loss = self._compute_loss(p, batch, rrng, theta)
                    return loss * scale / gas

                sloss, g = jax.value_and_grad(scaled_loss)(params)
                acc = jax.tree.map(lambda a, gg: a + gg[None], acc, g)
                loss = jax.lax.pmean(sloss, axis) * gas / scale
                return acc, loss

            acc, loss = smap(
                body,
                in_specs=(P(), P(axis), P(), P(axis), P(), P()),
                out_specs=(P(axis), P()), **smap_kw)(
                    state.params, state.acc_grads, state.scale.loss_scale,
                    batch, rng, pld_theta)
            return state._replace(acc_grads=acc), loss

        def apply_step(state):
            lr = self._lr_fn_traced(state.step)

            def body(params, opt_state, acc, inv_scale, lr):
                grads = jax.tree.map(lambda a: a[0] * inv_scale, acc)

                def do(op):
                    p, o = op
                    updates, new_o = self.optimizer.update(grads, o, p, lr)
                    return jax.tree.map(jnp.add, p, updates), new_o

                if cfg.fp16_enabled:
                    bad = sum(
                        (~jnp.isfinite(g).all()).astype(jnp.int32)
                        for g in jax.tree.leaves(grads))
                    finite = jax.lax.psum(bad, axis) == 0
                    new_params, new_opt = jax.lax.cond(
                        finite, do, lambda op: op, (params, opt_state))
                else:
                    finite = jnp.bool_(True)
                    new_params, new_opt = do((params, opt_state))
                zeros = jax.tree.map(jnp.zeros_like, acc)
                return new_params, new_opt, zeros, finite

            new_params, new_opt, zeros, finite = smap(
                body,
                in_specs=(P(), opt_spec, P(axis), P(), P()),
                out_specs=(P(), opt_spec, P(axis), P()),
                **smap_kw)(
                    state.params, state.opt_state, state.acc_grads,
                    1.0 / state.scale.loss_scale, lr)
            new_scale = update_scale(
                state.scale, ~finite,
                dynamic=self._dynamic_scale,
                scale_window=cfg.fp16.loss_scale_window,
                min_scale=cfg.fp16.min_loss_scale,
                delayed_shift=cfg.fp16.hysteresis)
            state = state._replace(
                params=new_params, opt_state=new_opt, acc_grads=zeros,
                scale=new_scale, step=state.step + finite.astype(jnp.int32))
            # grad clipping is excluded by _validate_onebit_config, so no
            # global norm is computed (get_global_grad_norm -> None)
            return state, jnp.float32(0.0), ~finite

        sh = self.state_shardings
        scalar = NamedSharding(self.mesh, P())
        self._jit_micro = jax.jit(
            micro_step, donate_argnums=0,
            in_shardings=(sh, None, None, None),
            out_shardings=(sh, scalar))
        self._jit_apply = jax.jit(
            apply_step, donate_argnums=0,
            in_shardings=(sh,),
            out_shardings=(sh, scalar, scalar))
        self._jit_train = None          # gas loop path drives train_batch
        self._jit_offload_pre = None    # offload excluded by validation
        self._need_norm = False
        self._jit_eval = jax.jit(
            lambda params, batch: self._compute_loss(params, batch, None))
        self._install_aot_steps()

    # ------------------------------------------------------- cost explorer
    def _get_cost_explorer(self):
        """One CostExplorer per engine: chip detection / memory_stats run
        once, and its warn-once pre-flight state persists across calls."""
        if getattr(self, "_cost_explorer_obj", None) is None:
            from deepspeed_tpu.telemetry.cost_explorer import CostExplorer
            self._cost_explorer_obj = CostExplorer.from_config(
                self.config.telemetry, registry=self.telemetry.registry)
        return self._cost_explorer_obj

    def _on_step_compiled(self, name, compiled):
        """First-dispatch hook from _AOTStep: census the artifact and run
        the HBM watermark pre-flight BEFORE the program first executes."""
        from deepspeed_tpu.telemetry.hlo_census import census_compiled
        if name not in ("fused_train_step", "micro_step"):
            # apply_step (and any future auxiliary) is owned for artifact
            # reuse only — the per-step census/pre-flight describe the
            # TRAIN program, which an auxiliary must never overwrite
            return
        # the fused step supersedes the micro census (it is the whole
        # program); a micro census never overwrites a fused one
        if self._cost_census is not None and \
                self._cost_census_program == "fused_train_step":
            return
        self._cost_census = census_compiled(compiled, mesh=self.mesh)
        self._cost_census_program = name
        if not self.telemetry.enabled:
            return
        explorer = self._get_cost_explorer()
        if getattr(self.config.telemetry, "cost_explorer_preflight", True):
            explorer.preflight(self._cost_census, name=name)
        explorer.publish(self._cost_census)

    def _aot_step_for(self, name):
        """The ``_AOTStep`` dispatcher behind a step entry point (unwraps
        the compile-watch layer), or None when the cost explorer is off /
        the program does not exist in this configuration."""
        attr = {"fused_train_step": "_jit_train",
                "micro_step": "_jit_micro",
                "apply_step": "_jit_apply"}.get(name)
        if attr is None:
            return None
        fn = getattr(self, attr, None)
        if fn is None:
            return None
        target = getattr(fn, "_compile_watch_target", fn)
        return target if isinstance(target, _AOTStep) else None

    def adopt_compiled_step(self, compiled_map, batch):
        """Prime this engine's owned-AOT dispatchers with EXTERNALLY
        compiled artifacts (``{program_name: jax.stages.Compiled}`` from
        an abstract twin's ``lower_step_programs().compile()``), so the
        first train step executes them instead of paying a fresh XLA
        compile — the autotuner's stage-1 -> stage-2 handoff, and the
        reason a whole tune run compiles each candidate exactly once.

        ``batch`` is one dispatch's batch (shapes only — used to build
        the signature the dispatcher matches against). Per-program the
        handoff mirrors the census-before-first-step path in
        ``get_cost_census``: signature FIRST, then artifact, then the
        census/pre-flight hook. Returns the set of adopted program
        names; a name is skipped (never an error) when the cost explorer
        is off, the program is already primed, or the signature cannot
        be computed — the dispatcher then falls back to the plain jit,
        which is correct, just not compile-free."""
        adopted = set()
        if not self._cost_explorer_on:
            logger.warning(
                "adopt_compiled_step: telemetry.cost_explorer is off — "
                "no _AOTStep dispatchers to prime; the first step will "
                "compile")
            return adopted
        # signature from ShapeDtypeStructs — _AOTStep._signature only
        # reads shape/dtype/sharding, so nothing is placed on device
        # just to compute a match key (SDS leaves have no `committed`
        # attribute -> sharding unconstrained, same as the uncommitted
        # rng/theta scalars at real dispatch; the batch SDS carries the
        # same NamedSharding _globalize_batch would commit)
        with self.mesh:
            batch_sds, rng_sds, theta_sds = \
                self._abstract_step_args(batch)
        for name, compiled in compiled_map.items():
            aot_step = self._aot_step_for(name)
            if aot_step is None or aot_step.compiled is not None:
                continue
            args = ((self.state,) if name == "apply_step"
                    else (self.state, batch_sds, rng_sds, theta_sds))
            try:
                sig = aot_step._signature(args)
            except Exception:
                sig = None
            if sig is None:
                continue
            aot_step.compiled, aot_step._sig = compiled, sig
            self._on_step_compiled(name, compiled)
            adopted.add(name)
        return adopted

    def get_cost_census(self, batch=None):
        """Static census (flops / bytes / memory / per-axis collectives)
        of the engine's active step program.

        Zero-compile when the cost explorer owns the artifact (the
        ``telemetry.cost_explorer.enabled`` path) — otherwise ONE AOT
        compile of the already-traced program is paid and the result
        memoized (the price the old flops profiler paid on every
        ``start_profile``). ``batch`` is only needed when no step has run
        yet (falls back to ``_last_batch``)."""
        if self._cost_census is not None:
            return self._cost_census
        from deepspeed_tpu.telemetry.hlo_census import census_compiled
        if batch is None:
            batch = self._last_batch
        assert batch is not None, (
            "get_cost_census before any train step needs an example "
            "batch: pass batch=...")
        target, name = self._jit_train, "fused_train_step"
        if target is None:
            target, name = self._jit_micro, "micro_step"
        # unwrap compile-watch, then reach the jit under a possible
        # _AOTStep (whose artifact would have been used above if primed)
        target = getattr(target, "_compile_watch_target", target)
        aot_step = target if isinstance(target, _AOTStep) else None
        if aot_step is not None:
            if aot_step.compiled is not None:
                self._cost_census = census_compiled(aot_step.compiled,
                                                    mesh=self.mesh)
                self._cost_census_program = name
                return self._cost_census
            target = aot_step._jit
        if aot_step is None:
            logger.info(
                "[cost-explorer] no owned compiled artifact (enable "
                "telemetry.cost_explorer to keep one); paying one AOT "
                "compile of %r for the census", name)
        with self.mesh:
            gbatch = self._globalize_batch(batch) \
                if batch is not self._last_batch else batch
            args = (self.state, gbatch, self._next_rng(), jnp.float32(1.0))
            compiled = target.lower(*args).compile()
        if aot_step is not None:
            # census-before-first-step: this compile IS the training
            # compile — hand the artifact to the dispatcher so the first
            # train step reuses it instead of compiling again (the AOT
            # path has no cache of its own), and run the usual
            # census/pre-flight/gauge hook. Signature FIRST: assigning
            # compiled without a matching _sig would half-prime the
            # dispatcher and send every step to the cold fallback jit.
            try:
                sig = aot_step._signature(args)
            except Exception:
                sig = None
            if sig is not None:
                aot_step.compiled, aot_step._sig = compiled, sig
            self._on_step_compiled(name, compiled)
        else:
            self._cost_census = census_compiled(compiled, mesh=self.mesh)
            self._cost_census_program = name
        return self._cost_census

    def explain_step(self, batch=None, step_time_s=None):
        """Explain the compiled step: roofline/MFU attribution, compute/
        memory/comm-bound verdict, per-axis collective bytes, and the HBM
        watermark — joined from the static census and measured step time
        (the telemetry step-time histogram, else the throughput timer,
        else static-only). Returns the report dict; publishes the census
        gauges through the telemetry registry when enabled."""
        census = self.get_cost_census(batch=batch)
        if step_time_s is None:
            reg = self.telemetry.registry
            if reg is not None:
                h = reg.histogram("train_step_time_ms",
                                  "host wall time per train_batch")
                if h.count > 1 and self._first_step_time_ms is not None:
                    # exclude the first step: its wall time is dominated
                    # by XLA compilation, not execution — averaging it in
                    # would understate MFU by the compile/steady ratio
                    step_time_s = ((h.sum - self._first_step_time_ms)
                                   / (h.count - 1) / 1e3)
                elif h.count:
                    step_time_s = h.sum / h.count / 1e3
            if step_time_s is None:
                sps = self.tput_timer.avg_samples_per_sec()
                if sps > 0:
                    step_time_s = self.train_batch_size() / sps
        explorer = self._get_cost_explorer()
        # under gradient accumulation the census covers ONE micro step but
        # the measured step time covers gas of them (+ the small apply
        # program, uncounted) — scale the rate math accordingly
        invocations = (self.gradient_accumulation_steps()
                       if self._cost_census_program == "micro_step" else 1)
        report = explorer.explain(
            census, step_time_s=step_time_s,
            name=self._cost_census_program or "step",
            invocations=invocations)
        report["aot_artifact_owned"] = self._cost_explorer_on
        if self.telemetry.enabled:
            explorer.publish(census, report)
        return report

    # ------------------------------------------------- health observatory
    def _wire_health_monitor(self):
        """Fill the rank-0 HealthMonitor's mesh/config-dependent fields
        once the bucket spec exists (manager built it before the step fns
        were constructed, so it could not know them)."""
        mon = self.telemetry.health
        if mon is None:
            return
        mon.bucket_names = list(self._health_spec.names)
        if self.config.fp16_enabled:
            mon.min_scale = float(self.config.fp16.min_loss_scale)
        mon.census_fn = self._census_header

    def _census_header(self):
        """Compact cost-census header for HEALTH.json (None when the cost
        explorer never censused a program)."""
        c = self._cost_census
        if c is None:
            return None
        return {"program": self._cost_census_program,
                "flops_per_device": c.flops,
                "bytes_accessed": c.bytes_accessed,
                "hbm_watermark_bytes": c.hbm_watermark_bytes,
                "n_devices": c.n_devices}

    def _health_tick(self, force=False):
        """Fetch + observe the pending in-step stats at the health cadence
        (default ``steps_per_print``) — the ONLY host<->device sync in the
        health path; between ticks the host holds device references only.
        Rank 0 only (the monitor gates it); other ranks never fetch."""
        mon = self.telemetry.health
        if (mon is None or not self._health_on
                or self._pending_health_stats is None):
            return None
        cadence = self._health_cadence or self.steps_per_print()
        if not force and self.global_steps % cadence != 0:
            return None
        if self._health_last_obs_step == self.global_steps:
            return mon.last_sample
        self._health_last_obs_step = self.global_steps
        # ONE transfer for the whole tick (stats pytree + loss scalar) —
        # every device_get is a blocking sync, and avoidable round-trips
        # are this engine's cardinal sin. The loss is the last dispatched
        # micro/fused loss (the fused path's loss IS the global loss;
        # under gas>1 it is the last micro's).
        with self._led_attr("device_compute"):
            stats, loss_arr = jax.device_get(
                (self._pending_health_stats, self._health_last_loss))
        loss = (float(np.asarray(loss_arr))
                if loss_arr is not None else None)
        sample = {
            "step": self.global_steps,
            "loss": loss,
            "lr": self.get_lr()[0],
            "skipped_steps": self.skipped_steps,
            "grad_norm": float(stats["grad_norm"]),
            "param_norm": float(stats["param_norm"]),
            "update_ratio": float(stats["update_ratio"]),
            "bucket_grad_norms": [
                float(x) for x in np.asarray(
                    stats["bucket_grad_norms"]).ravel()],
            "nonfinite_buckets": int(stats["nonfinite_buckets"]),
            "loss_scale": float(stats["loss_scale"]),
            "good_steps": int(stats["good_steps"]),
            "hysteresis": int(stats["hysteresis"]),
            "overflow": bool(stats["overflow"]),
        }
        mon.observe(sample)
        reg = self.telemetry.registry
        if reg is not None:
            reg.gauge("train_param_norm",
                      "global param L2 norm (health stats)").set(
                          sample["param_norm"])
            reg.gauge("train_update_ratio",
                      "||applied update|| / ||params|| (health stats)").set(
                          sample["update_ratio"])
            reg.gauge("health_nonfinite_buckets",
                      "non-finite grad provenance bitmask").set(
                          sample["nonfinite_buckets"])
            for name, v in zip(self._health_spec.names,
                               sample["bucket_grad_norms"]):
                reg.gauge("train_grad_norm_bucket",
                          "per-module-bucket grad L2 norm",
                          labels={"bucket": name}).set(v)
        return sample

    def health_report(self, write=False):
        """The training-health forensics report (what HEALTH.json holds):
        verdict, anomaly history, EWMA state, the recent-stats ring and
        the cost-census header. Forces one stats fetch so the report is
        current even between cadences. ``write=True`` also writes the
        snapshot file. ``{"enabled": False}`` when ``telemetry.health``
        is off or this is not rank 0."""
        mon = self.telemetry.health
        if mon is None or not self._health_on:
            return {"enabled": False}
        self._health_tick(force=True)
        if write:
            mon.write_snapshot(force=True)
        return mon.report()

    # ------------------------------------------- HBM residency observatory
    @staticmethod
    def _leaf_device_bytes(arr):
        """Physical device bytes one state leaf pins across this
        process's addressable devices — shard bytes x addressable
        shards. Pure metadata arithmetic (shape/dtype/sharding), never a
        device sync; a replicated leaf on an 8-device mesh costs 8x its
        logical nbytes in HBM, which is what the profile's live total
        sees (plain ``arr.nbytes`` would undercount it 8x)."""
        try:
            sh = arr.sharding
            shard = sh.shard_shape(tuple(arr.shape))
            n = len(sh.addressable_devices)
            return int(np.prod(shard, dtype=np.int64)) * \
                int(arr.dtype.itemsize) * n
        except Exception:
            return int(getattr(arr, "nbytes", 0) or 0)

    def _memory_build_inventory(self):
        """Expected device bytes for the engine-owned pools, split
        through the PR-3 bucket names. Static after init (the accounting
        is shape metadata), so it is built once and cached. Optimizer
        moments and the grad-accumulation pool mirror the param tree, so
        their leaves map back to the same module buckets by path
        component; unmatched leaves fold into ``(other)``."""
        if self._memory_inventory is not None:
            return self._memory_inventory
        from deepspeed_tpu.telemetry.health import (_path_component,
                                                    build_bucket_spec)
        spec = self._health_spec or build_bucket_spec(
            self.state.params,
            depth=int(getattr(self.config.telemetry,
                              "health_bucket_depth", 8)))
        flat, _ = jax.tree_util.tree_flatten_with_path(self.state.params)
        param_buckets = {name: 0 for name in spec.names}
        for (path, leaf), b in zip(flat, spec.leaf_buckets):
            param_buckets[spec.names[b]] += self._leaf_device_bytes(leaf)

        def bucket_of(path):
            comps = {_path_component(e) for e in path}
            for name in spec.names:
                if all(p in comps for p in name.split("/")):
                    return name
            return "(other)"

        opt_buckets = {name: 0 for name in spec.names}
        opt_bytes = 0
        for tree in (self.state.opt_state,
                     getattr(self.state, "acc_grads", None)):
            oflat, _ = jax.tree_util.tree_flatten_with_path(tree)
            for path, leaf in oflat:
                b = self._leaf_device_bytes(leaf)
                opt_bytes += b
                name = bucket_of(path)
                opt_buckets[name] = opt_buckets.get(name, 0) + b
        self._memory_inventory = {
            "totals": {"params": sum(param_buckets.values()),
                       "optimizer_state": opt_bytes,
                       "kv_pool": 0},
            "param_buckets": param_buckets,
            "opt_buckets": {k: v for k, v in opt_buckets.items() if v},
        }
        return self._memory_inventory

    def _memory_arm(self, mon):
        """Fill the monitor's census/mesh-dependent fields lazily: the
        pre-flight watermark prediction (once the cost explorer has
        censused a step program) and the HBM budget — a real device
        ``memory_stats`` limit only; the host-RSS fallbacks are refused
        (warn-once) because process RSS is not an HBM budget."""
        if mon.predicted_bytes is None:
            hdr = self._census_header()
            if hdr and hdr.get("hbm_watermark_bytes"):
                per_dev = int(hdr["hbm_watermark_bytes"])
                n = int(hdr.get("n_devices") or 1)
                mon.set_prediction(
                    per_dev * n, source="cost_explorer.preflight",
                    detail={"hbm_watermark_bytes_per_device": per_dev,
                            "n_devices": n,
                            "program": hdr.get("program")})
        if mon.budget_bytes is None and not self._memory_budget_checked:
            self._memory_budget_checked = True
            from deepspeed_tpu.telemetry.metrics import device_memory_stats
            stats = device_memory_stats()
            src = stats.get("source")
            if src == "device" and stats.get("bytes_limit"):
                mon.set_budget(
                    int(stats["bytes_limit"]) * len(jax.local_devices()),
                    source="jax.memory_stats")
            elif src in ("host_rss", "host_peak_rss"):
                mon.refuse_host_budget(src)

    def _memory_tick(self, force=False):
        """Fetch + attribute one device-memory profile at the memory
        cadence (default ``steps_per_print``) — a host RPC into the
        runtime's allocator bookkeeping, never a device sync and never a
        program change (the train step stays byte-identical; the
        telemetry_overhead guard pins 0 extra compiles). Rank 0 only
        (the monitor gates it)."""
        mon = self._memory
        if mon is None:
            return None
        cadence = self._memory_cadence or self.steps_per_print()
        if not force and self.global_steps % cadence != 0:
            return None
        if self._memory_last_obs_step == self.global_steps:
            return mon.last_sample
        self._memory_last_obs_step = self.global_steps
        self._memory_arm(mon)
        try:
            from deepspeed_tpu.telemetry import memory_observatory as _mo
            from deepspeed_tpu.telemetry import pprof as _pprof
            sample = _mo.profile_sample(_pprof.fetch_device_memory_profile())
        except Exception as e:
            if not self._memory_warned_fetch:
                self._memory_warned_fetch = True
                logger.warning(
                    "[memory] device memory profile unavailable on this "
                    "backend: %s — residency windows disabled", e)
            return None
        inv = self._memory_build_inventory()
        sample["step"] = self.global_steps
        sample["inventory"] = inv["totals"]
        sample["param_buckets"] = inv["param_buckets"]
        sample["opt_buckets"] = inv["opt_buckets"]
        mon.observe(sample)
        reg = self.telemetry.registry
        if reg is not None:
            for name, c in mon.last_attribution["categories"].items():
                reg.gauge("memory_live_bytes",
                          "attributed live device bytes",
                          labels={"category": name}).set(c["bytes"])
            reg.gauge("memory_peak_bytes",
                      "measured peak live device bytes").set(
                          mon.measured_peak_bytes)
        return sample

    def memory_report(self, write=False):
        """The HBM residency report (what MEMORY_ANATOMY.json holds):
        exact-sum category/bucket attribution of the live profile, the
        measured-vs-predicted watermark drift, budget state, anomaly
        history and the window ring. Forces one profile fetch so the
        report is current even between cadences. ``write=True`` also
        writes the report file. ``{"enabled": False}`` when
        ``telemetry.memory`` is off or this is not rank 0."""
        mon = self._memory
        if mon is None:
            return {"enabled": False}
        self._memory_tick(force=True)
        if write:
            mon.write_report()
        return mon.report()

    # --------------------------------------------------- goodput ledger
    def _led_attr(self, category):
        """Goodput wall-clock attribution context for *category*; the
        shared no-op when the ledger is off (sub-µs, like trace_span).
        Ranks whose manager (and therefore ledger) is disabled but whose
        fleet shipper is live still time input-wait and checkpoint
        intervals — the cross-rank skew rules need every rank's numbers,
        not just rank 0's."""
        led = self._goodput
        if led is None:
            if self._fleet is not None and category in (
                    "input_wait", "checkpoint_save"):
                return self._fleet.time_category(category)
            return _NULL_CTX
        return led.attribute(category)

    def _breakdown_summary(self):
        """The goodput report's ``wall_clock_breakdown`` section, read
        from the SAME recorded timer intervals the breakdown log prints
        (``timer_<phase>_ms`` histograms) — one step loop, one timing
        system, two views that cannot disagree."""
        if not self.wall_clock_breakdown():
            return None
        reg = self.telemetry.registry
        if reg is None:
            return None
        phases = {}
        families = reg.collect()
        for name in (FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                     STEP_GLOBAL_TIMER):
            fam = families.get(f"timer_{name}_ms")
            if not fam:
                continue
            h = fam[0]
            phases[name] = {"total_ms": round(h.sum, 3), "count": h.count}
        return {
            "note": "recorded by the wall_clock_breakdown timers; the "
                    "synced phase intervals are attributed to the "
                    "ledger's device_compute category",
            "phases": phases,
        }

    def goodput_report(self, write=False):
        """The wall-clock goodput ledger report (what ``GOODPUT.json``
        holds): per-category seconds summing to elapsed wall time,
        goodput fraction, per-window ring, badput anomalies and the
        profiler-capture state. Closes the current partial window first
        so the report is current. ``write=True`` also writes the
        snapshot file. ``{"enabled": False}`` when ``telemetry.goodput``
        is off or this is not rank 0."""
        led = self._goodput
        if led is None or not led.enabled:
            return {"enabled": False}
        led.tick(self.global_steps, force=True)
        report = led.report()
        if write:
            led.write_snapshot(force=True, report=report)
        return report

    # --------------------------------------------------- step anatomy
    def profile_step(self, steps=None, batch=None, out=None, write=True):
        """Measured device-time attribution for *steps* train steps.

        Runs a bounded ``jax.profiler`` capture around N annotated
        ``train_batch`` calls, post-processes the XSpace trace with the
        dependency-free xplane parser, joins the per-op device events to
        the engine's own compiled HLO (``op_name`` module paths, census
        collectives, CostExplorer roofline floors) and writes the
        schema-pinned ``STEP_ANATOMY.json``. The capture reuses the
        already-primed step signature — zero additional train-step
        compiles. Inert (returns ``{"enabled": False}``) when
        ``telemetry.anatomy`` is off or the profiler is unavailable.

        ``batch`` defaults to the last trained batch; when the engine
        has never stepped, one warmup step runs OUTSIDE the capture
        window so compile time never pollutes the measured anatomy."""
        from deepspeed_tpu.telemetry import step_anatomy
        from deepspeed_tpu.telemetry.ledger import (
            profiler_available, _start_trace, _stop_trace)
        tcfg = self.config.telemetry
        if not getattr(tcfg, "anatomy_enabled", True):
            return {"enabled": False,
                    "reason": "telemetry.anatomy.enabled is false"}
        if not profiler_available():
            return {"enabled": False,
                    "reason": "jax.profiler programmatic capture "
                              "unavailable"}
        steps = int(steps if steps is not None
                    else getattr(tcfg, "anatomy_capture_steps", 3))
        if batch is None:
            batch = self._last_batch
        assert batch is not None, (
            "profile_step before any train step needs an example batch: "
            "pass batch=...")
        if self.global_steps == 0:
            # prime the compiled signature outside the window: the XLA
            # compile would otherwise dominate (and distort) step 0
            self.train_batch(batch=batch)
        outdir = getattr(tcfg, "output_path", "") or "telemetry/"
        trace_dir = os.path.join(outdir, "anatomy_profile")
        os.makedirs(trace_dir, exist_ok=True)
        try:
            _start_trace(trace_dir)
        except Exception as e:
            return {"enabled": False,
                    "reason": f"profiler start_trace failed: {e}"}
        try:
            from jax.profiler import TraceAnnotation
            for i in range(steps):
                with TraceAnnotation(step_anatomy.STEP_MARK, step=i):
                    loss = self.train_batch(batch=batch)
                    # block INSIDE the annotation so the device work of
                    # this step lands inside its window
                    jax.block_until_ready(loss)
        finally:
            try:
                _stop_trace()
            except Exception as e:
                logger.warning("[anatomy] stop_trace failed: %s", e)
        report = step_anatomy.summarize_capture(
            trace_dir, **self._anatomy_join_inputs())
        if report is None:
            return {"enabled": False,
                    "reason": f"profiler wrote no .xplane.pb under "
                              f"{trace_dir}"}
        report["enabled"] = True
        report.setdefault("source", {})["global_step"] = self.global_steps
        if write:
            path = out or getattr(tcfg, "anatomy_report_file", "") \
                or os.path.join(outdir, "STEP_ANATOMY.json")
            step_anatomy.write_report(report, path)
            report["report_path"] = path
        self._export_anatomy_lanes(report, trace_dir, outdir)
        # cap retained raw trace runs (the summary JSON survives)
        keep = int(getattr(tcfg, "anatomy_keep_raw_traces", 2))
        runs = sorted(
            (r for r in glob.glob(os.path.join(
                trace_dir, "plugins", "profile", "*")) if os.path.isdir(r)),
            key=os.path.getmtime, reverse=True)
        for stale in runs[keep:]:
            shutil.rmtree(stale, ignore_errors=True)
        return report

    def _anatomy_join_inputs(self):
        """The engine-owned join inputs for a step-anatomy capture: HLO
        op table + bucket names + roofline floors + census collective
        schedule. Everything is best-effort and NEVER compiles — a
        missing artifact just degrades attribution to name heuristics."""
        op_table = None
        schedule = None
        try:
            aot = (self._aot_step_for("fused_train_step")
                   or self._aot_step_for("micro_step"))
            if aot is not None and aot.compiled is not None:
                from deepspeed_tpu.telemetry import step_anatomy
                from deepspeed_tpu.telemetry.hlo_census import (
                    collective_schedule_positions)
                hlo_text = aot.compiled.as_text()
                op_table = step_anatomy.hlo_op_table(hlo_text)
                schedule = collective_schedule_positions(hlo_text)
        except Exception as e:
            logger.warning("[anatomy] HLO op-table join unavailable: %s", e)
        floors = None
        try:
            if self._cost_census is not None:
                floors = self.explain_step().get("bound_floors_s")
        except Exception as e:
            logger.warning("[anatomy] roofline floors unavailable: %s", e)
        buckets = (list(self._health_spec.names)
                   if self._health_spec is not None else None)
        return {"op_table": op_table, "bucket_names": buckets,
                "predicted_floors": floors,
                "schedule_positions": schedule}

    def _export_anatomy_lanes(self, report, trace_dir, outdir):
        """Merge the capture's per-device lanes into the Chrome trace
        (tracer spans + device lanes, via fleet.merge_traces) when span
        tracing is on. Best-effort: a merge failure only costs the
        merged view, never the report."""
        tel = self.telemetry
        if not (tel.enabled and getattr(tel, "tracer", None) is not None
                and tel.tracer.enabled):
            return
        try:
            from deepspeed_tpu.telemetry import step_anatomy, xplane
            from deepspeed_tpu.telemetry.fleet import merge_traces
            files = xplane.find_xplane_files(trace_dir)
            if not files:
                return
            _, lanes = step_anatomy.extract_events(
                xplane.parse_xspace_file(files[0]))
            if not lanes:
                return
            dev_path = os.path.join(outdir, "anatomy_device.trace.json")
            step_anatomy.write_device_trace(dev_path, lanes)
            host_path = tel.tracer.export(
                os.path.join(outdir, "anatomy_host.trace.json"))
            merged = merge_traces(
                os.path.join(outdir, "anatomy_merged.trace.json"),
                [host_path, dev_path])
            report["merged_trace"] = merged
        except Exception as e:
            logger.warning("[anatomy] device-lane trace merge failed: %s",
                           e)

    # --------------------------------------------------- fleet recorder
    def _resolve_desync(self):
        """Arm the desync sentinel when the engine is inside its
        envelope: data-parallel replicas that are REPLICATED in name
        (zero <= 2, no model/expert/pipe sharding of params) are the
        precondition for cross-replica checksum comparison — a sharded
        param tree diverges across ranks by design. A perf/forensics
        knob, never a semantic switch: outside the envelope the fleet
        still ships, just without checksums (warn once)."""
        bad = []
        if self.dp_world_size < 2:
            bad.append("data-parallel world size 1 (no replicas to "
                       "cross-check)")
        if self.zero_stage >= 3:
            bad.append(f"zero stage {self.zero_stage} (params sharded "
                       "over dp — replicas legitimately differ)")
        if self.mp_world_size != 1:
            bad.append("model parallelism")
        if groups.get_expert_parallel_world_size() != 1:
            bad.append("expert parallelism")
        if groups.get_pipe_parallel_world_size() != 1:
            bad.append("pipeline parallelism")
        if not bad:
            # belt and braces: the checksum shard_map assumes every leaf
            # is fully replicated; any partitioned spec would make the
            # per-device reduction read different (legitimate) slices
            specs = {tuple(s.spec) for s in
                     jax.tree_util.tree_leaves(self.param_shardings)}
            if any(any(e is not None for e in spec) for spec in specs):
                bad.append("partitioned param shardings")
        if bad:
            if not self._warned_desync:
                self._warned_desync = True
                logger.warning(
                    "telemetry.fleet.desync requested but the parameter "
                    "checksum sentinel is disabled — incompatible with: "
                    + "; ".join(bad))
            return False
        return True

    def _fleet_tick(self, force=False):
        """Ship this rank's window record at the fleet cadence (and run
        the rank-0 aggregation poll). The only device access is the
        cadence-gated desync checksum fetch on THIS (main) thread —
        attributed like the health tick; the shipping itself is host
        file I/O on the background writer."""
        fl = self._fleet
        if fl is None:
            return None
        cad = self._fleet_cadence or self.steps_per_print()
        if not force and self.global_steps % cad != 0:
            return None
        desync = None
        if self._desync_on and self._desync_fn is not None and \
                fl.has_pending_steps() and \
                self._fleet_ticks % self._desync_every == 0:
            with self._led_attr("device_compute"), \
                    self.telemetry.span("fleet/desync_checksum"):
                mat = jax.device_get(self._desync_fn(self.state.params))
            desync = {
                "step": self.global_steps,
                "bucket_names": list(self._desync_spec.names),
                "replicas": [[i, [float(v) for v in row]]
                             for i, row in enumerate(mat)],
            }
        mon = self.telemetry.health
        health = mon.last_sample if (mon is not None
                                     and self._health_on) else None
        rec = fl.tick(step=self.global_steps,
                      skipped_steps=self.skipped_steps,
                      desync=desync, health=health, force=force)
        if rec is not None:
            self._fleet_ticks += 1
        if self._fleet_monitor is not None:
            # rank 0 merges whatever every rank (this one included) has
            # shipped so far; pure host file I/O, judged incrementally.
            # Only the forced report path waits for the background
            # writer — draining every cadence tick would park the train
            # thread on the writer's fsync (on a shared fs that can be
            # tens of ms), and the monitor simply judges this rank's
            # window on the next poll once the file lands.
            if force:
                fl.drain()
            self._fleet_monitor.poll(force=force)
        return rec

    def fleet_report(self, write=False):
        """The fleet flight-recorder report (what ``FLEET_HEALTH.json``
        holds): per-rank exact-integer window sums, the merged window
        ring with cross-rank skew views, desync sentinel state and the
        fired anomalies. Ships this rank's partial window first so the
        report is current. On a non-zero fleet rank (no aggregator)
        returns the shipper's own summary. ``{"enabled": False}`` when
        ``telemetry.fleet`` is off."""
        if self._fleet is None:
            return {"enabled": False}
        self._fleet_tick(force=True)
        if self._fleet_monitor is None:
            return {"enabled": True, "role": "shipper",
                    "rank": self._fleet.rank,
                    "windows_shipped": self._fleet.windows_shipped,
                    "ship_errors": self._fleet.ship_errors}
        report = self._fleet_monitor.report()
        if write:
            self._fleet_monitor.write_snapshot(force=True, report=report)
        return report

    # ----------------------------------------------------------- guardian
    def guardian_report(self, write=False):
        """The guardian's action journal (what ``GUARDIAN.json`` holds):
        armed policies, rules seen, every action taken with its trigger
        rule and outcome. ``{"enabled": False}`` when the guardian is
        off (or disarmed by multi-process)."""
        if self._guardian is None:
            return {"enabled": False}
        report = self._guardian.report()
        if write:
            self._guardian.write_journal()
        return report

    # ---------------------------------------------------------- chronicle
    def _chronicle_emit(self, phase, **data):
        """Engine-lifecycle event into the run chronicle. No-op unless
        THIS engine armed one (one attribute test when off — the
        autotuner's trial engines must not cross-chronicle)."""
        if self._chronicle is not None and self._chronicle.enabled:
            self._chronicle.emit("lifecycle", source="engine",
                                 step=int(self.global_steps), phase=phase,
                                 **data)

    def _note_first_compile(self, step_s):
        """The first train_batch is the compile-dominated one — a
        timeline without it misattributes minutes of wait to whatever
        fired next."""
        if not self._chronicle_first_emitted:
            self._chronicle_first_emitted = True
            self._chronicle_emit(
                "first_compile", step_time_ms=round(step_s * 1000.0, 3),
                detail="first train_batch (compile-dominated)")

    def chronicle_report(self, write=False):
        """The run chronicle + correlated incidents (what
        ``CHRONICLE.json`` / ``INCIDENTS.json`` hold): this rank's merged
        causal event timeline, plus the incident chains the correlator
        joins out of it — ordered member events, ranked root cause,
        goodput cost re-added from the ledger's window-diff events.
        Works on a closed engine (reads the in-memory log; ``write=True``
        then writes both artifacts synchronously).
        ``{"enabled": False}`` when ``telemetry.chronicle`` is off."""
        if self._chronicle is None:
            return {"enabled": False}
        from deepspeed_tpu.telemetry import incidents as _inc
        tcfg = self.config.telemetry
        doc = self._chronicle.report()
        doc["incidents"] = _inc.correlate(
            self._chronicle.snapshot_events(),
            step_window=int(getattr(tcfg, "chronicle_step_window", 8)),
            time_window_us=int(round(float(getattr(
                tcfg, "chronicle_time_window_s", 30.0)) * 1e6)),
            job_name=tcfg.job_name or "")
        if write:
            self._chronicle.drain()
            self._chronicle.write_summary(self._chronicle_summary_path)
            _inc.write_incidents(doc["incidents"],
                                 self._chronicle_incidents_path)
        return doc

    def _guardian_emergency_save(self, step):
        """Guardian action (a): an extra checkpoint through the normal
        save path (async writer when configured, one in flight). The tag
        is prefixed so rollback can de-prioritize it — state saved
        BECAUSE something looked wrong is of unknown health."""
        from deepspeed_tpu.runtime.guardian import EMERGENCY_TAG_PREFIX
        save_dir = self._guardian_ckpt_dir
        if save_dir is None:
            raise RuntimeError(
                "no checkpoint directory known yet (the guardian learns "
                "it from the first user save_checkpoint())")
        tag = f"{EMERGENCY_TAG_PREFIX}_step{int(step)}"
        self.save_checkpoint(save_dir, tag=tag,
                             data_iter=self._guardian_data_iter,
                             initiator="guardian")
        return tag

    def _guardian_rollback(self):
        """Guardian action (b): restore the newest intact tag — params,
        optimizer state, loss-scale state and the data-stream position —
        through the normal load path. Prefers user tags over the
        guardian's own emergency tags (those may hold exactly the state
        this rollback exists to escape); the whole interval books as
        ``checkpoint_load`` badput."""
        from deepspeed_tpu.runtime import checkpoint_io
        from deepspeed_tpu.runtime.guardian import EMERGENCY_TAG_PREFIX
        save_dir = self._guardian_ckpt_dir
        if save_dir is None:
            raise RuntimeError(
                "no checkpoint directory known yet (the guardian learns "
                "it from the first user save_checkpoint())")
        try:
            names = os.listdir(save_dir)
        except OSError:
            names = []
        emergency = [n for n in names
                     if n.startswith(EMERGENCY_TAG_PREFIX)]
        tag = checkpoint_io.newest_intact_tag(save_dir, exclude=emergency)
        if tag is None and emergency:
            tag = checkpoint_io.newest_intact_tag(save_dir)
        if tag is None:
            raise RuntimeError(
                f"no intact checkpoint tag under {save_dir} to roll "
                f"back to")
        with self.telemetry.span("guardian/rollback", tag=str(tag)):
            path, _ = self.load_checkpoint(
                save_dir, tag=tag, data_iter=self._guardian_data_iter)
        if path is None:
            raise RuntimeError(f"rollback load of tag {tag!r} failed")
        return tag

    def _guardian_fp16_rescue(self):
        """Guardian action (c): reset the dynamic loss scaler out of
        collapse — an escape scale with fresh good-step count and
        hysteresis. The LR schedule is traced INTO the compiled step
        program, so the scaler state (same shapes/dtypes, zero
        recompiles) is the intervention surface."""
        if not self.config.fp16_enabled:
            raise RuntimeError("fp16_rescue on a non-fp16 engine")
        old_scale = float(jax.device_get(self.state.scale.loss_scale))
        old_hyst = int(jax.device_get(self.state.scale.hysteresis))
        new_scale = max(old_scale * 16.0, 16.0)
        self.state = self.state._replace(scale=LossScaleState(
            loss_scale=jnp.float32(new_scale),
            good_steps=jnp.int32(0),
            hysteresis=jnp.int32(max(old_hyst, 2))))
        return f"loss_scale {old_scale:g} -> {new_scale:g}"

    def _lr_fn_traced(self, step):
        """LR schedule on a traced step: the four built-in schedules are
        written in jnp so they compile straight into the apply step."""
        return jnp.asarray(self._lr_fn(step), jnp.float32)

    # ------------------------------------------------------------------ train
    def _next_rng(self):
        key = jax.random.PRNGKey(self._seed)
        return jax.random.fold_in(key, self.micro_steps)

    def _apply_curriculum(self, batch):
        """Truncate sequence dims to the scheduled difficulty (reference
        engine.py:1577-1583 injects curriculum_seqlen; here the engine
        slices the batch — each plateau compiles once)."""
        from deepspeed_tpu.runtime.data_pipeline.curriculum_scheduler \
            import apply_seqlen_truncation
        return apply_seqlen_truncation(self.curriculum_scheduler,
                                       self.global_steps, batch)

    def forward(self, batch):
        """Compute loss for one micro-batch (and, fused, its gradients).

        Returns the unscaled loss as a jax scalar. The reference's separate
        autograd backward is folded in (see module docstring)."""
        if self.curriculum_scheduler is not None:
            batch = self._apply_curriculum(batch)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        theta = jnp.float32(
            self.progressive_layer_drop.get_theta()
            if self.progressive_layer_drop is not None else 1.0)
        breakdown = self.wall_clock_breakdown()
        # goodput: with the breakdown syncs on, this region is device-bound
        # wall time (the block_until_ready wait); async, it is dispatch
        with self._led_attr("device_compute" if breakdown
                            else "host_dispatch"):
            if breakdown:
                self.timers(FORWARD_GLOBAL_TIMER).start()
            with self.telemetry.span("forward", micro_step=self.micro_steps):
                with self.mesh:
                    batch = self._globalize_batch(batch)
                    self.state, loss = self._jit_micro(
                        self.state, batch, self._next_rng(), theta)
            if breakdown:
                jax.block_until_ready(loss)
                self.timers(FORWARD_GLOBAL_TIMER).stop(record=True)
        self._pending_loss = loss
        self._last_batch = batch
        if self._health_on:
            self._health_last_loss = loss   # device ref, no sync
        return loss

    def _globalize_batch(self, batch, for_train=True, verify=True):
        """Place the host batch onto the mesh as the GLOBAL batch.

        ``verify=False`` is the background-thread (prefetch device
        stage) contract: placement itself is collective-free — the
        cross-process verification collectives (broadcast-leaf checksum
        allgather, eval row-count agreement) are DEFERRED to
        ``_verify_prefetched_batch`` on the main thread at consumption.
        A background-thread collective racing main-thread collectives is
        a deadlock, which is why PR 5 disabled the device stage on
        multi-process runs; splitting verification out of the placement
        path is what lifted that restriction.

        A scalar, or a dim0==1 leaf in a batch whose OTHER leaves carry
        real rows (a [1,S] broadcast mask, a shared table), is NOT a
        per-row batch slice — it is replicated whole (round-4 advisory:
        the old blanket row check spuriously rejected these, and the
        single-process device_put tried to row-shard them). A batch
        whose every leaf has one row is NOT reinterpreted — that shape
        is a mis-sliced loader, and the loud uneven-rows rejection was
        built for exactly that. Single process: device_put against the
        per-leaf sharding. Multi process: each host holds only its
        slice (deepspeed_io loads global_micro/process_count rows), so
        the global array is assembled from per-process shards —
        device_put would silently treat the local slice as the whole
        batch (ADVICE round 1); broadcast leaves are checksum-verified
        identical across processes before being stamped 'replicated'.

        A batch the prefetcher's device stage already placed (runtime/
        prefetch.py) arrives here as global jax arrays with exactly the
        shardings this function computes — the single-process
        ``device_put`` below then returns the SAME buffers without a
        transfer (same object, re-checked under jax 0.9), so re-entering
        is the cheap, validation-preserving way to "skip" placement."""
        import numpy as _np
        shardings = self._batch_sharding(batch)
        n_proc = jax.process_count()
        global_rows = (self.train_micro_batch_size_per_gpu()
                       * self.dp_world_size)
        expect = global_rows // n_proc  # batch rows each process holds
        repl = NamedSharding(self.mesh, P())
        all_single_row = all(
            _np.ndim(x) == 0 or _np.shape(x)[0] == 1
            for x in jax.tree.leaves(batch))

        def _is_broadcast(x):
            # only on the DEFAULT sharding path: an explicit batch_spec
            # is the user's word and is honored verbatim for every leaf
            if self._batch_spec is not None:
                return False
            if _np.ndim(x) == 0:
                return True
            # eval batches are not bound to the train micro-batch size,
            # so there dim0==1 in a mixed tree is broadcast regardless
            return (_np.shape(x)[0] == 1 and not all_single_row
                    and (expect != 1 or not for_train))

        if (for_train and (self._onebit_dist or self._sparse_grads
                           or self._comm_overlap_on)
                and any(_is_broadcast(x) and _np.ndim(x) > 0
                        for x in jax.tree.leaves(batch))):
            # the 1-bit / sparse-grad / comm-overlap TRAIN step fns
            # shard_map the whole batch tree with in_specs=P(data) — a
            # dim0==1 leaf fails divisibility there with an opaque trace
            # error, so reject it loudly here (eval_batch jits without
            # shard_map and handles replicated leaves fine)
            raise NotImplementedError(
                "broadcast batch leaves (leading dim 1) are not supported "
                "with 1-bit optimizers, sparse_gradients or comm_overlap: "
                "their step functions shard the whole batch over the data "
                "axis; give the leaf the batch's leading dimension")
        shardings = jax.tree.map(
            lambda x, sh: repl if _is_broadcast(x) else sh,
            batch, shardings)
        if n_proc == 1:
            return jax.device_put(batch, shardings)
        # A batch the prefetch device stage already placed arrives as
        # GLOBAL (non-fully-addressable) arrays — re-running placement
        # would np.asarray them, which raises. Run the deferred
        # cross-process verification the background thread skipped
        # (verify=False placement) and hand the same buffers back.
        batch_leaves = jax.tree.leaves(batch)
        if batch_leaves and all(
                isinstance(x, jax.Array) and not x.is_fully_addressable
                for x in batch_leaves):
            # verify=False is the background thread (a user loader can
            # yield pre-placed global arrays straight into the device
            # stage): the verification collectives stay deferred to the
            # main-thread re-globalize at consumption, which lands in
            # this same branch with verify=True
            if verify:
                self._verify_prefetched_batch(batch, for_train=for_train)
            return batch
        # Validate the WHOLE tree before any placement or collective so a
        # uniform loader bug raises on every rank instead of deadlocking
        # a later collective (rank-DIVERGENT tree shapes can still hang —
        # the same failure class as any diverged SPMD program).
        for x, sh in zip(jax.tree.leaves(batch),
                         jax.tree.leaves(shardings)):
            if _is_broadcast(x):
                continue
            # replicated BATCH sharding can't be assembled from differing
            # per-process slices — every host would need the FULL batch
            if sh.is_fully_replicated:
                raise NotImplementedError(
                    "multi-process run with a replicated batch sharding: "
                    "each process only loads its slice (deepspeed_io), so "
                    "a replicated global batch cannot be assembled; use a "
                    "data-parallel mesh axis or load the full batch per "
                    "process via model_parameters/batch_spec")
            rows = _np.shape(x)[0]
            if for_train and rows != expect:
                raise ValueError(
                    f"uneven per-process batch slice: this process holds "
                    f"{rows} rows but the global micro-batch "
                    f"({global_rows}) over {n_proc} processes requires "
                    f"exactly {expect} per process (deepspeed_io slices "
                    f"evenly; feed each rank its own equal slice; "
                    f"broadcast leaves must have leading dim 1)")
            if not for_train and verify:
                # eval batches are not bound to the train micro-batch
                # geometry, but ranks must still agree on the row count —
                # a mismatch would compile divergent programs and hang
                # at the next collective instead of raising. verify=False
                # (background placement) defers this agreement check to
                # _verify_prefetched_batch on the main thread.
                from jax.experimental import multihost_utils
                all_rows = _np.asarray(multihost_utils.process_allgather(
                    _np.asarray([rows], _np.int64)))
                if not (all_rows == rows).all():
                    raise ValueError(
                        f"eval batch slices disagree across processes: "
                        f"row counts {sorted(set(all_rows.ravel().tolist()))}"
                        f" — every rank must feed an equal slice")

        def _place(path, x, sh):
            if _is_broadcast(x) and verify:
                # make_array_from_process_local_data does not cross-check
                # replicated content, so a mis-sliced loader feeding each
                # rank a different single row would silently diverge —
                # checksum-verify the first time each leaf path is seen
                # (steady-state cost zero; content drift after the first
                # batch is the cross-rank-assert debug tier's job).
                # verify=False (background placement) defers the checksum
                # to _verify_prefetched_batch on the main thread — the
                # allgather is a collective and this may be a background
                # thread.
                key = (tuple(str(p) for p in path), _np.shape(x),
                       str(_np.asarray(x).dtype))
                if key not in self._broadcast_leaves_checked:
                    self._broadcast_leaves_checked.add(key)
                    self._assert_identical_across_processes(x)
            return jax.make_array_from_process_local_data(
                sh, _np.asarray(x))

        return jax.tree_util.tree_map_with_path(_place, batch, shardings)

    def _assert_identical_across_processes(self, x):
        """Raise if ``x``'s bytes differ on any process (sha256 checksum
        allgather; guards the replicated broadcast-leaf path)."""
        import hashlib

        import numpy as _np
        from jax.experimental import multihost_utils
        digest = hashlib.sha256(
            _np.ascontiguousarray(_np.asarray(x)).tobytes()).digest()
        h = _np.frombuffer(digest[:8], dtype=_np.uint64)
        all_h = _np.asarray(multihost_utils.process_allgather(h))
        if not (all_h == all_h.ravel()[0]).all():
            raise ValueError(
                "broadcast batch leaf (leading dim 1) differs across "
                "processes — a dim0==1 leaf is replicated whole, so every "
                "process must feed the identical array; if this leaf is "
                "really a per-process batch slice, give it the batch's "
                "leading dimension")

    def _verify_prefetched_batch(self, batch, for_train=True):
        """Main-thread half of the split placement: the cross-process
        verification collectives a ``verify=False`` (background-thread)
        placement deferred — the broadcast-leaf checksum allgather and,
        for eval routes, the row-count agreement check. Runs at
        consumption, BEFORE the batch is dispatched, keyed by the same
        first-occurrence sets the direct placement path uses (steady
        state cost: one set lookup per leaf)."""
        import numpy as _np
        eval_rows = []
        for path, x in jax.tree_util.tree_flatten_with_path(batch)[0]:
            sh = getattr(x, "sharding", None)
            shape = tuple(getattr(x, "shape", ()))
            if sh is not None and getattr(sh, "is_fully_replicated", False):
                key = (tuple(str(p) for p in path), shape, str(x.dtype))
                if key in self._broadcast_leaves_checked:
                    continue
                self._broadcast_leaves_checked.add(key)
                # the local copy of the replicated leaf: this process's
                # own contribution, exactly what placement checksummed
                self._assert_identical_across_processes(
                    _np.asarray(x.addressable_data(0)))
            elif not for_train and shape:
                eval_rows.append(int(shape[0]))
        if eval_rows:
            # UNCONDITIONAL per batch, like the direct placement path's
            # row check: caching this by shape would make the allgather
            # call COUNT diverge across ranks exactly when shapes
            # diverge — the silent-deadlock case the check exists to
            # turn into a clean raise. ONE vector allgather for all
            # leaves (not one per leaf): the per-leaf version taxed
            # every steady-state eval batch L serial round-trips.
            from jax.experimental import multihost_utils
            all_rows = _np.asarray(multihost_utils.process_allgather(
                _np.asarray(eval_rows, _np.int64)))
            if not (all_rows == all_rows.reshape(
                    -1, len(eval_rows))[0]).all():
                raise ValueError(
                    f"eval batch shapes disagree across processes "
                    f"after background placement: global row counts "
                    f"{sorted(set(all_rows.ravel().tolist()))} — "
                    f"every rank must feed an equal slice")

    def backward(self, loss=None, allreduce_gradients=True, release_loss=False):
        """Bookkeeping half of the fused forward/backward (see ``forward``)."""
        assert self._pending_loss is not None, "backward() requires a prior forward()"
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_GLOBAL_TIMER).start()
            self.timers(BACKWARD_GLOBAL_TIMER).stop(record=True)
        self._pending_loss = None
        self.micro_steps += 1
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps % self.gradient_accumulation_steps()) == 0

    def _compute_block_eigenvalues(self):
        """Per-block loss-Hessian eigenvalue ratios at the current params
        over the last trained batch (reference engine.py:1891)."""
        if self._last_batch is None:
            return {}
        batch = self._last_batch

        def loss_fn(p):
            return self._compute_loss(p, batch, jax.random.PRNGKey(0))

        with self.mesh:
            ev = self.eigenvalue.compute_block_eigenvalues(
                loss_fn, self.state.params)
        if ev:
            blocks = sorted({lid for _, lid in ev.values()})
            vals = {lid: r for r, lid in ev.values()}
            if self.monitor.enabled and self.monitor.monitors:
                # reference scalar names (engine.py:1926-1934)
                self.monitor.write_events([
                    (f"Train/Eigenvalues/ModelBlockParam_{i}", vals[i],
                     self.global_samples) for i in blocks])
        return ev

    def _offload_step(self):
        """Host half of the offloaded step: shard-local CPU-Adam."""
        self.state, grads, grad_norm, overflow = self._jit_offload_pre(
            self.state)
        if not bool(jax.device_get(overflow)):
            lr = float(self._lr_fn(max(
                0, self.global_steps - self.skipped_steps)))
            new_params = self._offload_opt.step(
                grads, lr, self.state.params, self.param_shardings)
            self.state = self.state._replace(
                params=new_params, step=self.state.step + 1)
        return grad_norm, overflow

    def step(self, lr_kwargs=None):
        """Optimizer step at the gradient-accumulation boundary
        (reference engine.step, engine.py:1862)."""
        if not self.is_gradient_accumulation_boundary():
            return
        breakdown = self.wall_clock_breakdown()
        with self._led_attr("device_compute" if breakdown
                            else "host_dispatch"):
            if breakdown:
                self.timers(STEP_GLOBAL_TIMER).start()
            with self.telemetry.span("step", global_step=self.global_steps):
                if self._offload:
                    grad_norm, overflow = self._offload_step()
                elif self._health_on:
                    self.state, grad_norm, overflow, stats = self._jit_apply(
                        self.state)
                    self._pending_health_stats = stats   # device refs only
                else:
                    self.state, grad_norm, overflow = self._jit_apply(
                        self.state)
            if breakdown:
                jax.block_until_ready(self.state.step)
                self.timers(STEP_GLOBAL_TIMER).stop(record=True)
            self._post_apply(grad_norm, overflow, lr_kwargs)

    def _post_apply(self, grad_norm, overflow, lr_kwargs=None):
        """Host bookkeeping after an applied (or skipped) optimizer step."""
        # device scalar only — the host float is cached at print cadence
        # (get_global_grad_norm's float contract); None (not a misleading
        # 0.0) when the step skipped computing it
        self._pending_grad_norm = grad_norm if self._need_norm else None
        self.global_steps += 1
        self.global_samples += self.train_batch_size()
        # only fp16 can overflow; skipping the device_get elsewhere keeps
        # the train loop free of a per-step host sync
        if self.config.fp16_enabled:
            with self._led_attr("device_compute"):
                overflowed = bool(jax.device_get(overflow))
        else:
            overflowed = False
        if self.quantizer is not None:
            # MoQ: progressive fake-quantization of the trained params
            # (reference _take_model_step hook, engine.py:1816-1827 —
            # skips on overflow so the bit schedule tracks applied steps).
            # When a precision switch is due and eigenvalue guidance is on,
            # spend a per-block curvature estimate first (reference
            # engine.py:1884-1904): its ratios stretch the next period of
            # sharp (high-curvature) blocks.
            if (self.eigenvalue is not None
                    and self.global_steps %
                    self.eigenvalue.gas_boundary_resolution == 0
                    and self.quantizer.any_precision_switch()):
                self.block_eigenvalue = self._compute_block_eigenvalues()
            quantized = self.quantizer.quantize(
                self.state.params, overflow=overflowed,
                eigenvalue_enabled=self.eigenvalue is not None,
                block_eigenvalue=self.block_eigenvalue)
            if quantized is not self.state.params:
                self.state = self.state._replace(
                    params=jax.device_put(quantized, self.param_shardings))
        if overflowed:
            # reference engine.py:1844-1854: scheduler does NOT advance on a
            # skipped step, keeping it in lock-step with the applied-lr index
            # (state.step, which also only advances on success).
            self.skipped_steps += 1
            log_dist(
                f"[deepspeed] OVERFLOW! skipping step; new loss scale: "
                f"{self.loss_scale}", ranks=[0])
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step(**(lr_kwargs or {}))
        led = self._goodput
        if led is not None:
            if overflowed:
                # the step just burned by the fp16 skip: re-label its
                # still-open wall-clock interval (the train_batch / step
                # wrapper) from good time to overflow_skipped badput
                led.reclassify_open("overflow_skipped")
            led.note_step(self.global_steps, overflowed)
            cad = self._goodput_cadence or self.steps_per_print()
            if self.global_steps % cad == 0:
                # pure host arithmetic — closes a ledger window, runs the
                # badput rules; never touches the device
                led.tick(self.global_steps)
        mon = self.telemetry.health
        if mon is not None and self._health_on:
            # host-only per-step facts (overflow streaks are exact, not
            # sampled); the stats fetch below is cadence-gated
            mon.note_step(self.global_steps, overflowed)
        sample = self._health_tick()
        self._memory_tick()
        if self.global_steps % self.steps_per_print() == 0 \
                and self._pending_grad_norm is not None:
            # the print path pays the device sync anyway; cache the float.
            # A health sample fetched this step already carries the same
            # scalar — reuse it rather than a second blocking device_get.
            self._last_grad_norm = (
                sample["grad_norm"] if sample is not None
                else float(jax.device_get(self._pending_grad_norm)))
        if self._slo is not None:
            # burn-rate evaluation (host arithmetic, self-throttled to
            # eval_interval_s) BEFORE the guardian tick so a page-tier
            # burn fired this step is actionable this step
            self._slo.tick(step=self.global_steps)
        if self._guardian is not None:
            # anomaly->action policies run HERE, on the main thread at
            # the step boundary — the only place swapping the live train
            # state (rollback, fp16 rescue) is safe. One attribute read
            # and a truthiness check when nothing is pending.
            self._guardian.tick(self.global_steps)

    def _fused_train_batch(self, data_iter, batch):
        """gas=1 fast path: one fused compiled program per global step.
        Returns the loss and what ``_post_apply`` takes (the caller runs
        it, under its ``train_post`` span)."""
        span = self.telemetry.span
        if batch is not None:
            micro = batch
        else:
            with span("train_input"), self._led_attr("input_wait"):
                micro = next(data_iter)
        if self.curriculum_scheduler is not None:
            micro = self._apply_curriculum(micro)
        if self.progressive_layer_drop is not None:
            self.progressive_layer_drop.update_state(self.global_steps)
        theta = jnp.float32(
            self.progressive_layer_drop.get_theta()
            if self.progressive_layer_drop is not None else 1.0)
        with span("fused_step", global_step=self.global_steps), self.mesh:
            with span("train_place"):
                gbatch = self._globalize_batch(micro)
            with span("train_dispatch"):
                if self._health_on:
                    (self.state, loss, grad_norm, overflow,
                     stats) = self._jit_train(
                         self.state, gbatch, self._next_rng(), theta)
                    self._pending_health_stats = stats   # device refs only
                    self._health_last_loss = loss
                else:
                    self.state, loss, grad_norm, overflow = self._jit_train(
                        self.state, gbatch, self._next_rng(), theta)
        self._pending_loss = None
        self._last_batch = gbatch   # flops profiler reads this
        self.micro_steps += 1
        return loss, (grad_norm, overflow)

    def train_batch(self, data_iter=None, batch=None):
        """One full global step: gas micro-batches + optimizer step."""
        if data_iter is not None:
            if self._guardian is not None:
                # rollback rewinds the LIVE loader (the same PR-7 resume
                # machinery as load_checkpoint(data_iter=...)) — keep a
                # handle to the caller's raw iterator, pre-prefetch-wrap
                self._guardian_data_iter = data_iter
            data_iter = self._maybe_prefetch_iter(data_iter)
        tel = self.telemetry
        if not tel.enabled:
            if self._fleet is None:
                with tel.span("train_batch", global_step=self.global_steps):
                    return self._train_batch(data_iter, batch)
            # non-zero fleet ranks: the manager (and ledger) are rank-0
            # only, but the fleet needs THIS rank's step wall times —
            # two clock reads, nothing else
            t0 = time.perf_counter()
            with tel.span("train_batch", global_step=self.global_steps):
                mean_loss = self._train_batch(data_iter, batch)
            step_s = time.perf_counter() - t0
            self._fleet.note_step_time(step_s)
            self._note_first_compile(step_s)
            self._fleet_tick()
            return mean_loss
        t0 = time.perf_counter()
        # goodput: the whole step interval is host_dispatch SELF time —
        # nested attributions (input_wait in next(), compile via the
        # backend listener, the print-cadence device fetches) subtract
        # themselves out; an fp16 overflow re-labels it in _post_apply.
        # Step boundary FIRST: the previous step's trailing intervals
        # (its wrapper, the publish fetch) booked after its note_step,
        # and must not be sweepable by THIS step's overflow.
        if self._goodput is not None:
            self._goodput.mark_step_begin()
        with self._led_attr("host_dispatch"):
            with tel.span("train_batch", global_step=self.global_steps):
                mean_loss = self._train_batch(data_iter, batch)
            step_s = time.perf_counter() - t0
            self._note_first_compile(step_s)
            self._publish_step_telemetry(mean_loss, step_s)
        if self._fleet is not None:
            self._fleet.note_step_time(step_s)
            self._fleet_tick()
        return mean_loss

    def _tokens_per_sample(self):
        """Best-effort tokens/sample from the last batch's shape (first
        integer [B, S, ...] leaf); 0 when the workload has no token dim."""
        if self._last_batch is None:
            return 0
        for x in jax.tree.leaves(self._last_batch):
            if getattr(x, "ndim", 0) >= 2 and \
                    jnp.issubdtype(jnp.asarray(x).dtype, jnp.integer):
                return int(x.shape[1])
        return 0

    def _publish_step_telemetry(self, mean_loss, step_s):
        """Per-step metric publication (telemetry enabled only).

        Host-side metrics move EVERY step; gauges that read device values
        (loss, grad norm, loss scale) only publish at ``steps_per_print``
        cadence, where the existing log line already pays the device sync
        — telemetry must not add a per-step host<->device round trip."""
        reg = self.telemetry.registry
        reg.counter("train_steps_total",
                    "global steps (applied + skipped)").inc()
        reg.counter("train_samples_total",
                    "training samples consumed").inc(self.train_batch_size())
        reg.histogram("train_step_time_ms",
                      "host wall time per train_batch").observe(
                          step_s * 1000.0)
        if self._first_step_time_ms is None:
            # remembered so explain_step can exclude the compile-dominated
            # first step from its steady-state step-time estimate
            self._first_step_time_ms = step_s * 1000.0
        if self.global_steps % self.steps_per_print() != 0:
            return
        with self._led_attr("device_compute"):
            # the one blocking loss fetch of the print cadence
            reg.gauge("train_loss", "loss at the last print step").set(
                float(jax.device_get(mean_loss)))
            if self.config.fp16_enabled:
                reg.gauge("train_loss_scale", "dynamic loss scale").set(
                    self.loss_scale)
        reg.gauge("train_lr", "lr of the next applied step").set(
            self.get_lr()[0])
        if self._last_grad_norm is not None:
            # already a host float — _post_apply cached it at this cadence
            reg.gauge("train_grad_norm",
                      "global grad norm of the last applied step").set(
                          self._last_grad_norm)
        reg.gauge("train_skipped_steps",
                  "overflow-skipped optimizer steps").set(self.skipped_steps)
        sps = self.tput_timer.avg_samples_per_sec()
        if sps > 0:
            reg.gauge("samples_per_sec",
                      "running average samples/sec").set(sps)
            tokens = self._tokens_per_sample()
            if tokens:
                reg.gauge("tokens_per_sec",
                          "running average tokens/sec").set(sps * tokens)
        self.telemetry.publish_device_memory()
        self.telemetry.flush()

    def _train_batch(self, data_iter=None, batch=None):
        fp_cfg = self.config.flops_profiler_config
        profiling = (self.flops_profiler is not None
                     and self.global_steps == fp_cfg.profile_step)
        profile_t0 = time.perf_counter() if profiling else 0.0
        self.tput_timer.start()
        span = self.telemetry.span
        applied = None
        if self._jit_train is not None:
            mean_loss, applied = self._fused_train_batch(data_iter, batch)
        else:
            losses = []
            for _ in range(self.gradient_accumulation_steps()):
                if batch is not None:
                    micro = batch
                else:
                    assert data_iter is not None
                    with span("train_input"), self._led_attr("input_wait"):
                        micro = next(data_iter)
                loss = self.forward(micro)
                self.backward(loss)
                losses.append(loss)
            self.step()
            mean_loss = jnp.mean(jnp.stack(losses))
        with span("train_post"):
            if applied is not None:
                self._post_apply(*applied)
            self.tput_timer.stop(global_step=True)
            self._report_step(mean_loss, profiling, profile_t0)
        return mean_loss

    def _report_step(self, mean_loss, profiling, profile_t0):
        """What a finished step owes its observers: the print-cadence log
        line, the one-shot flops profile, the wall-clock breakdown and the
        monitor's scalars."""
        fp_cfg = self.config.flops_profiler_config
        if self.global_steps % self.steps_per_print() == 0:
            # float(mean_loss) is a blocking device fetch: wall time spent
            # here is the device catching up — good time, device_compute
            with self._led_attr("device_compute"):
                log_dist(
                    f"step={self.global_steps} loss={float(mean_loss):.6f} "
                    f"lr={self.get_lr()[0]:.3e}", ranks=[0])
        if profiling:
            # one-shot at profile_step (reference engine.py:1722-1952):
            # attribute the just-traced step's flops per module and print
            jax.block_until_ready(mean_loss)
            self.flops_profiler.start_profile()
            self.flops_profiler._duration = time.perf_counter() - profile_t0
            self.flops_profiler.print_model_profile(
                profile_step=fp_cfg.profile_step,
                module_depth=fp_cfg.module_depth,
                top_modules=fp_cfg.top_modules,
                detailed=fp_cfg.detailed,
                output_file=fp_cfg.output_file)
            self.flops_profiler.end_profile()
        if self.wall_clock_breakdown():
            self._breakdown_steps += 1
            if self.global_steps % self.steps_per_print() == 0:
                names = [FORWARD_GLOBAL_TIMER, BACKWARD_GLOBAL_TIMER,
                         STEP_GLOBAL_TIMER]
                if self.monitor.enabled and self.monitor.monitors:
                    means = self.timers.get_mean(
                        names, normalizer=self._breakdown_steps, reset=False)
                    # reference scalar names (engine.py:2015-2037)
                    self.monitor.write_events([
                        (f"Train/Samples/elapsed_time_ms_{n}", means[n],
                         self.global_samples) for n in names if n in means])
                self.timers.log(
                    names, normalizer=self._breakdown_steps,
                    memory_breakdown=self.config.memory_breakdown)
                self._breakdown_steps = 0
        if self.monitor.enabled and self.monitor.monitors \
                and self.global_steps % self.steps_per_print() == 0:
            # reference scalar names (engine.py:1686/:1911), sampled at
            # print cadence: the reference writes per step, but
            # float(mean_loss)/loss_scale force a host<->device sync and
            # per-step syncs are this engine's cardinal sin (see the
            # round-3/4 advisories) — the print step already pays it.
            # float(mean_loss)/loss_scale block on the device: goodput
            # books the wait as device_compute
            with self._led_attr("device_compute"):
                self.monitor.write_events([
                    ("Train/Samples/train_loss", float(mean_loss),
                     self.global_samples),
                    ("Train/Samples/lr", self.get_lr()[0],
                     self.global_samples),
                    ("Train/Samples/loss_scale", self.loss_scale,
                     self.global_samples),
                    # host-side counter that was computed but never
                    # exported (reference writes it via its monitor at
                    # the same point)
                    ("Train/Samples/skipped_steps",
                     float(self.skipped_steps), self.global_samples),
                ])

    def eval_batch(self, batch):
        with self._led_attr("eval"), self.telemetry.span("eval_batch"):
            with self.mesh:
                batch = self._globalize_batch(batch, for_train=False)
                return self._jit_eval(self.state.params, batch)

    def __call__(self, batch):
        return self.eval_batch(batch)

    # ------------------------------------------------------------------- data
    def deepspeed_io(self, dataset, batch_size=None, route=None,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        import deepspeed_tpu.comm as dist
        # Each process loads its host's slice of the global micro-batch.
        per_process = (self.train_micro_batch_size_per_gpu() *
                       self.dp_world_size) // dist.get_process_count()
        loader = DeepSpeedDataLoader(
            dataset,
            batch_size=batch_size or per_process,
            shuffle=data_sampler is None,
            drop_last=(True if self.config.dataloader_drop_last is None
                       else self.config.dataloader_drop_last),
            collate_fn=collate_fn or self.collate_fn,
            data_sampler=data_sampler,
            num_local_io_workers=num_local_io_workers,
            process_index=dist.get_rank(),
            process_count=dist.get_process_count())
        if not self._prefetch_cfg.enabled:
            if num_local_io_workers and not self._warned_io_workers:
                # the knob is accepted for reference parity but the
                # synchronous loader collates on the consumer thread —
                # tell the user why nothing got faster
                self._warned_io_workers = True
                logger.warning(
                    f"num_local_io_workers={num_local_io_workers} has no "
                    f"effect while data_prefetch is disabled: the loader "
                    f"collates synchronously on the consumer thread. "
                    f"Enable the 'data_prefetch' config block (or set "
                    f"DS_DATA_PREFETCH=1) to run the input pipeline in "
                    f"the background with that worker count — host "
                    f"collate workers plus the device double-buffering "
                    f"stage, which runs on multi-process meshes too "
                    f"(placement is collective-free; verification stays "
                    f"on the main thread).")
            return loader
        wrapped = PrefetchLoader(
            loader, depth=self._prefetch_cfg.depth,
            num_workers=num_local_io_workers or 1,
            place_fn=self._prefetch_place_fn(
                for_train=route in (None, ROUTE_TRAIN)))
        self._prefetchers.append(wrapped)
        return wrapped

    def _prefetch_place_fn(self, for_train=True):
        """The prefetch device stage's placement fn — ``_globalize_batch``
        with ``verify=False`` on a background thread — or None when the
        stage must stay off (curriculum learning: the scheduled per-step
        truncation happens on the HOST batch after ``next()`` —
        pre-placing would pin the full-length batch and defeat the
        plateau compile; warns once).

        Multi-process runs ARE supported: ``verify=False`` placement is
        collective-free by construction (the broadcast-leaf checksum
        allgather and eval row-count agreement are deferred to
        ``_verify_prefetched_batch`` on the main thread at consumption),
        so the background thread can never race a main-thread collective
        — the deadlock that made PR 5 disable the stage is structurally
        impossible now.

        ``for_train`` follows the loader's route: an eval-route loader
        must place with eval semantics (replicated dim0==1 leaves, no
        train-only broadcast rejection) or the background placement
        would diverge from what ``eval_batch`` does on the main thread."""
        import functools
        pf = self._prefetch_cfg
        if not pf.to_device:
            return None
        if self.curriculum_scheduler is not None:
            if not self._warned_prefetch_host_only:
                self._warned_prefetch_host_only = True
                logger.warning(
                    "data_prefetch: device stage disabled under "
                    "curriculum learning (the scheduled truncation "
                    "slices the host batch per step); host-side "
                    "prefetch stays on")
            return None
        if for_train:
            return functools.partial(self._globalize_batch, verify=False)
        return functools.partial(self._globalize_batch, for_train=False,
                                 verify=False)

    def _maybe_prefetch_iter(self, data_iter):
        """Wrap a user-supplied ``train_batch`` iterator in the prefetch
        pipeline (cached by identity — one pipeline per iterator).
        Already-prefetching sources pass through untouched."""
        if data_iter is None or not self._prefetch_cfg.enabled:
            return data_iter
        if isinstance(data_iter, PrefetchIterator):
            return data_iter
        # a RepeatingLoader over a deepspeed_io-built PrefetchLoader is
        # already prefetch-backed — don't stack a second pipeline on it
        if isinstance(getattr(data_iter, "loader", None), PrefetchLoader):
            return data_iter
        if hasattr(data_iter, "state_dict") \
                and hasattr(data_iter, "load_state_dict"):
            # a STATEFUL iterator (RepeatingLoader) counts its position
            # in __next__ — a background puller wrapped OUTSIDE it would
            # advance (epoch, batch_in_epoch) up to `depth` batches ahead
            # of what training consumed, and save_checkpoint(data_iter=)
            # would record a future position (a resumed run would skip
            # those batches). The correct composition is the pipeline
            # INSIDE the counter: RepeatingLoader over a prefetch-enabled
            # deepspeed_io loader.
            if not self._warned_prefetch_stateful:
                self._warned_prefetch_stateful = True
                logger.warning(
                    f"data_prefetch: not wrapping the stateful iterator "
                    f"{type(data_iter).__name__!r} passed to train_batch "
                    f"(a background puller would advance its resume "
                    f"counters ahead of consumption); build the loader "
                    f"via engine.deepspeed_io(...) and wrap THAT in "
                    f"RepeatingLoader to get prefetch AND deterministic "
                    f"resume")
            return data_iter
        cached = self._prefetch_wrap_cache.get(id(data_iter))
        if cached is not None and cached[0] is data_iter:
            return cached[1]
        # drop closed pipelines so exhausted iterators don't accumulate
        # (the strong source ref in the cache is what keeps id() valid)
        self._prefetch_wrap_cache = {
            k: v for k, v in self._prefetch_wrap_cache.items()
            if not v[1]._closed}
        wrapped = PrefetchIterator(
            data_iter, depth=self._prefetch_cfg.depth,
            place_fn=self._prefetch_place_fn())
        self._prefetch_wrap_cache[id(data_iter)] = (data_iter, wrapped)
        return wrapped

    def close(self):
        """Engine teardown: drain the async checkpoint writer (an
        in-flight save finishes, a failed one re-raises HERE — its last
        chance to surface), stop the prefetch pipelines (joins their
        worker threads) and close the telemetry manager. Idempotent; the
        pipelines and the writer also self-finalize at GC/interpreter
        exit, so this is the orderly path, not the only one."""
        try:
            if self._ckpt_writer is not None:
                with self._led_attr("checkpoint_save"):
                    self._ckpt_writer.close()
        finally:
            self._gc.close()
            if self._fleet_aggregator is not None:
                try:
                    # before the obs server: the aggregator's routes are
                    # mounted on it, and close() persists cursors + the
                    # final fleet snapshot while peers are still known
                    self._fleet_aggregator.close()
                except Exception as e:
                    logger.warning("[federation] close failed: %s", e)
            if self._obs_server is not None:
                from deepspeed_tpu.telemetry import obs_server as _obs_mod
                try:
                    # FIRST: stop serving scrapes before the monitors the
                    # providers point at are torn down underneath them
                    self._obs_server.close()
                except Exception as e:
                    logger.warning("[obs] server close failed: %s", e)
                _obs_mod.reset_obs_server(if_current=self._obs_server)
            for pl in self._prefetchers:
                pl.close()
            for _src, wrapped in list(self._prefetch_wrap_cache.values()):
                wrapped.close()
            self._prefetch_wrap_cache.clear()
            # drop the owned AOT artifacts and cached device refs: a
            # closed engine must not pin compiled executables or batch
            # buffers alive (the autotuner runs many trial engines in one
            # process — leaked artifacts would accumulate per probe)
            for name in ("fused_train_step", "micro_step", "apply_step"):
                aot_step = self._aot_step_for(name)
                if aot_step is not None:
                    aot_step.compiled = None
                    aot_step._sig = None
            self._cost_census = None
            self._cost_census_program = None
            self._last_batch = None
            if self._fleet is not None:
                from deepspeed_tpu.telemetry import fleet as _fleet_mod
                try:
                    # ship the final partial window and judge it before
                    # the writer thread goes away (anomalies whose last
                    # firings rode the snapshot throttle still land)
                    self._fleet_tick(force=True)
                except Exception as e:
                    logger.warning("[fleet] final tick failed: %s", e)
                self._fleet.close()
                _fleet_mod.reset_shipper(if_current=self._fleet)
            if self._fleet_monitor is not None:
                self._fleet_monitor.close()
            if self._guardian is not None:
                try:
                    # final journal (only when there is something to
                    # explain) — before telemetry goes away
                    self._guardian.close()
                except Exception as e:
                    logger.warning("[guardian] final journal failed: %s", e)
            if self._slo is not None:
                try:
                    # final burn snapshot while the registry histograms
                    # and the ledger are still live
                    self._slo.close()
                except Exception as e:
                    logger.warning("[slo] close failed: %s", e)
            self.telemetry.close()
            if self._chronicle is not None:
                from deepspeed_tpu.telemetry import chronicle as _chron_mod
                # AFTER telemetry.close(): the ledger's final forced tick
                # just emitted its last goodput_window — the lifecycle
                # close must be the timeline's final event. Emit before
                # closing (the writer only drains pre-close events), then
                # detach the global so later engines start clean.
                self._chronicle_emit("close")
                try:
                    self._chronicle.close()
                except Exception as e:
                    logger.warning("[chronicle] close failed: %s", e)
                _chron_mod.reset_chronicle(if_current=self._chronicle)

    # ------------------------------------------------------------ checkpoints
    def _get_ckpt_name(self, checkpoints_path, tag):
        mp_rank = (self.mpu.get_model_parallel_rank()
                   if self.mpu is not None else 0)
        return os.path.join(checkpoints_path, str(tag),
                            f"mp_rank_{mp_rank:02d}" + MODEL_FILE_SUFFIX)

    def _get_zero_ckpt_name(self, checkpoints_path, tag):
        import deepspeed_tpu.comm as dist
        pp_rank = dist.get_rank()
        return os.path.join(checkpoints_path, str(tag),
                            f"zero_pp_rank_{pp_rank}_mp_rank_00" + OPTIM_FILE_SUFFIX)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, data_iter=None, initiator="user"):
        """Shard-aware save: every process writes its addressable shards of
        params + optimizer state to its zero_pp_rank file (reference
        per-rank partition files, engine.py:2345); process 0 additionally
        writes metadata (and full params when it can address them) to the
        model-states file, the per-tag completeness manifest, and the
        'latest' tag pointer (engine.py:2889).

        Two-phase (CheckFreq snapshot-then-persist): the SNAPSHOT copies
        device state to host at the step boundary — the only phase the
        train loop (and the goodput ledger's ``checkpoint_save``
        category) pays for when ``checkpoint.async_save`` is on; the
        PERSIST phase (pickle + fsync + atomic rename + manifest) then
        runs on a background writer while training continues. A second
        save drains the in-flight one first, and a background write
        failure re-raises here (or at close()) rather than vanishing.

        ``data_iter``: a :class:`RepeatingLoader` (or anything exposing
        ``state_dict``) whose stream position is carried in the
        checkpoint, so a preempted run resumes its exact batch stream.

        ``initiator``: who asked for this save — ``"user"`` (default) or
        ``"guardian"`` for the policy engine's emergency saves. Carried
        on the checkpoint spans so a trace distinguishes the two."""
        if tag is None:
            tag = f"global_step{self.global_steps}"
        tag = str(tag)
        if self._guardian is not None and initiator == "user":
            # the guardian's emergency-save / rollback actions need a
            # checkpoint directory; the user's own saves teach it one
            self._guardian_ckpt_dir = save_dir
        if self._ckpt_writer is not None:
            # one save in flight, ever: drain the previous persist so two
            # saves can never interleave files or race the latest pointer
            # (the wait is honest checkpoint badput)
            with self._led_attr("checkpoint_save"):
                self._ckpt_writer.drain()
        with self._led_attr("checkpoint_save"), \
                self.telemetry.span("checkpoint/save", tag=tag,
                                    initiator=initiator):
            self._validate_checkpoint_tag(tag)
            os.makedirs(os.path.join(save_dir, tag), exist_ok=True)
            snapshot = self._snapshot_checkpoint(client_state, data_iter)
        if not self._ckpt_async:
            with self._led_attr("checkpoint_save"), \
                    self.telemetry.span("checkpoint/persist", tag=tag,
                                        initiator=initiator):
                self._persist_checkpoint(save_dir, tag, snapshot,
                                         save_latest)
            log_dist(f"saved checkpoint {save_dir}/{tag}", ranks=[0])
            self._chronicle_emit("checkpoint_save", tag=tag, dir=save_dir,
                                 initiator=initiator, mode="sync")
            return True
        reg = self.telemetry.registry
        if reg is not None:
            reg.counter("checkpoint_async_saves_total",
                        "async (snapshot-then-persist) saves started").inc()
        self._get_ckpt_writer().submit(
            lambda: self._persist_checkpoint(save_dir, tag, snapshot,
                                             save_latest), tag=tag)
        log_dist(f"checkpoint {save_dir}/{tag}: snapshot taken, "
                 f"persisting in background", ranks=[0])
        self._chronicle_emit("checkpoint_save", tag=tag, dir=save_dir,
                             initiator=initiator, mode="background")
        return True

    def _get_ckpt_writer(self):
        if self._ckpt_writer is None:
            from deepspeed_tpu.runtime.async_checkpoint import \
                AsyncCheckpointWriter
            self._ckpt_writer = AsyncCheckpointWriter(
                retries=self.config.checkpoint_persist_retries,
                backoff_s=self.config.checkpoint_persist_backoff_s)
        return self._ckpt_writer

    def _validate_checkpoint_tag(self, tag):
        if not self.config.checkpoint_tag_validation_enabled:
            return
        # reference _checkpoint_tag_validation (engine.py:2693) +
        # stage3's cross-rank consistency asserts: silently diverged
        # hosts must not write a mixed checkpoint. Collectives — always
        # on the main thread, never inside the background persist.
        from deepspeed_tpu.utils.debug import (
            assert_bytes_same_as_other_ranks,
            assert_ints_same_as_other_ranks,
            assert_shapes_same_as_other_ranks)
        try:
            assert_bytes_same_as_other_ranks(str(tag).encode(),
                                             tag="checkpoint-tag")
            assert_ints_same_as_other_ranks(
                [self.global_steps, self.micro_steps],
                tag="save_checkpoint")
            assert_shapes_same_as_other_ranks(self.state.params,
                                              tag="params")
        except AssertionError as e:
            if self.config.checkpoint_tag_validation_fail:
                raise
            log_dist(f"WARNING: cross-rank checkpoint mismatch "
                     f"({e}); writing anyway (validation mode Warn)",
                     ranks=[0])

    def _snapshot_checkpoint(self, client_state, data_iter):
        """Device->host snapshot of everything a save persists. With
        async_save the copies are FORCED (``copy=True`` / deepcopy): the
        train state is donated to the next step, so the background writer
        must own its bytes outright — a view into a donated buffer would
        pickle whatever the next step reused the memory for."""
        from deepspeed_tpu.runtime import checkpoint_io
        import deepspeed_tpu.comm as dist
        copy = self._ckpt_async
        with self.telemetry.span("checkpoint/gather_shards"):
            offload_sd = (self._offload_opt.state_dict()
                          if self._offload_opt else None)
            if copy and offload_sd is not None:
                import copy as _copy
                offload_sd = _copy.deepcopy(offload_sd)
            zero_sd = {
                "format": "shards-v1",
                "optimizer_state_dict": checkpoint_io.tree_local_shards(
                    self.state.opt_state, copy=copy),
                "offload_optimizer_state": offload_sd,
                "param_shards": checkpoint_io.tree_local_shards(
                    self.state.params, copy=copy),
                "scale_state": {k: np.array(jax.device_get(v), copy=True)
                                for k, v in
                                self.state.scale._asdict().items()},
                "zero_stage": self.zero_stage,
                "partition_count": self.dp_world_size,
            }
        snapshot = {"zero_sd": zero_sd, "params_tree": None, "meta": None}
        if dist.get_rank() != 0:
            return snapshot

        fully_addressable = all(
            getattr(x, "is_fully_addressable", True)
            for x in jax.tree.leaves(self.state.params))
        if fully_addressable:
            # the params are ALREADY host-side in param_shards — don't
            # copy them a second time on the critical path; the persist
            # phase reassembles the full model-states tree from the
            # shards (host numpy work, overlapped when async)
            paths, treedef = jax.tree_util.tree_flatten_with_path(
                self.state.params)
            snapshot["params_tree"] = (
                [jax.tree_util.keystr(p) for p, _ in paths], treedef)
        it_state = None
        if data_iter is not None:
            sd_fn = getattr(data_iter, "state_dict", None)
            if sd_fn is not None:
                it_state = sd_fn()
            else:
                logger.warning(
                    "save_checkpoint(data_iter=...): the iterator has no "
                    "state_dict(); the data-stream position is NOT saved "
                    "(wrap the loader in RepeatingLoader for "
                    "deterministic resume)")
        snapshot["meta"] = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "skipped_steps": self.skipped_steps,
            "micro_steps": self.micro_steps,
            "dp_world_size": self.dp_world_size,
            "mp_world_size": self.mp_world_size,
            "loss_scale": float(np.asarray(
                jax.device_get(self.state.scale.loss_scale))),
            "lr_scheduler": (self.lr_scheduler.state_dict()
                             if self.lr_scheduler else None),
            "data_iterator": it_state,
            "ds_config": self.config._param_dict,
            "ds_version": "tpu-0.1",
            "client_state": client_state or {},
        }
        return snapshot

    def _persist_checkpoint(self, save_dir, tag, snapshot, save_latest):
        """File half of a save — pure host I/O over the snapshot's
        numpy, safe on the background writer thread (no device access,
        no collectives). Durability order is the crash-consistency
        contract: per-rank shard files (each atomic), model states,
        THEN — after every rank's shard file exists — the completeness
        manifest, and only then the ``latest`` pointer. A kill anywhere
        leaves the previous checkpoint reachable and this tag
        detectably incomplete."""
        from deepspeed_tpu.runtime import checkpoint_io
        import deepspeed_tpu.comm as dist
        tag_dir = os.path.join(save_dir, tag)
        checkpoint_io.dump_file(snapshot["zero_sd"],
                                self._get_zero_ckpt_name(save_dir, tag),
                                kind="zero_states")
        if snapshot["meta"] is None:       # not rank 0
            return
        meta = snapshot["meta"]
        model_np = None
        if snapshot["params_tree"] is not None:
            # reassemble the full params from the snapshotted shards
            # (bit-identical to a direct device_get: the shards carry
            # their global indices)
            pstrs, treedef = snapshot["params_tree"]
            merged = checkpoint_io.assemble([snapshot["zero_sd"]
                                             ["param_shards"]])
            model_np = jax.tree_util.tree_unflatten(
                treedef, [merged[p] for p in pstrs])
        # MoE expert params get the reference's per-expert file layout
        # (engine.py:2780 _save_moe_checkpoint): one
        # layer_{L}_expert_{E}_mp_rank_XX file per global expert, with the
        # non-moe state in the model-states file
        moe_prefixes, moe_counts = [], []
        if model_np is not None and isinstance(model_np, dict):
            model_np, moe_prefixes, moe_counts = \
                checkpoint_io.save_moe_experts(tag_dir, model_np)
        sd = {
            "module": model_np,
            "has_moe_layers": bool(moe_prefixes),
            "moe_layer_prefixes": moe_prefixes,
            "moe_expert_counts": moe_counts,
            **meta,
        }
        checkpoint_io.dump_file(sd, self._get_ckpt_name(save_dir, tag),
                                kind="model_states")
        # durability gate: all ranks' shard files, via the shared
        # filesystem (file polling, deliberately collective-free — this
        # may be a background thread)
        n_proc = dist.get_process_count()
        expected = [os.path.join(
            tag_dir, f"zero_pp_rank_{r}_mp_rank_00" + OPTIM_FILE_SUFFIX)
            for r in range(n_proc)]
        checkpoint_io.wait_for_files(
            expected, timeout_s=self.config.checkpoint_wait_timeout_s,
            describe=f"all {n_proc} ranks' shard files of tag {tag!r}")
        # re-saving an existing tag from a SMALLER world must not leave
        # the old run's extra rank files behind: load's zero_pp_rank_*
        # glob would mix shards from two different optimizer states, and
        # the manifest below would certify the mix as intact
        import glob as _glob
        import re as _re
        for f in _glob.glob(os.path.join(
                tag_dir, "zero_pp_rank_*" + OPTIM_FILE_SUFFIX)):
            m = _re.search(r"zero_pp_rank_(\d+)_", os.path.basename(f))
            if m and int(m.group(1)) >= n_proc:
                os.remove(f)
        checkpoint_io.write_manifest(tag_dir, meta={
            "tag": tag,
            "global_steps": meta["global_steps"],
            "dp_world_size": meta["dp_world_size"],
            "processes": n_proc,
        })
        if save_latest:
            checkpoint_io.write_latest(save_dir, LATEST_FILE, tag)

    def _verify_load_tag(self, load_dir, tag, explicit_tag):
        """Gate every load on the tag's completeness manifest. An intact
        tag passes; a legacy (manifest-less) tag loads with a warning
        (per-file atomicity still rules out truncated pickles); a
        missing/empty/corrupt tag raises a clear error naming the tag
        and directory — or, for implicit (``latest``-resolved) loads
        with ``checkpoint.fallback_to_intact`` on, recovers to the
        newest intact tag."""
        from deepspeed_tpu.runtime import checkpoint_io
        tag_dir = os.path.join(load_dir, tag)
        status, detail = checkpoint_io.verify_tag(tag_dir)
        if status == "intact":
            return tag
        if status == "legacy":
            logger.warning(
                f"checkpoint tag {tag!r} at {tag_dir} has no completeness "
                f"manifest ({detail}); loading with per-file checks only")
            return tag
        source = ("requested" if explicit_tag
                  else "named by the 'latest' pointer")
        msg = (f"checkpoint tag {tag!r} ({source}) at {tag_dir} is not "
               f"loadable: {detail}")
        if explicit_tag or not self.config.checkpoint_fallback:
            raise (FileNotFoundError(msg) if status == "missing"
                   else RuntimeError(msg))
        fallback = checkpoint_io.newest_intact_tag(load_dir, exclude=(tag,))
        if fallback is None:
            raise (FileNotFoundError if status == "missing"
                   else RuntimeError)(
                msg + "; no intact fallback tag exists under "
                + str(load_dir))
        logger.warning(f"{msg}; falling back to the newest intact tag "
                       f"{fallback!r}")
        return fallback

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True, load_lr_scheduler_states=True,
                        load_module_only=False, data_iter=None):
        # the WHOLE restore interval books as checkpoint_load badput:
        # shard reassembly and device_put after the file reads used to
        # land in the unattributed residual (attribution is nesting-safe
        # — the inner read intervals just shrink this one's self time)
        with self._led_attr("checkpoint_load"):
            return self._load_checkpoint(
                load_dir, tag=tag, load_module_strict=load_module_strict,
                load_optimizer_states=load_optimizer_states,
                load_lr_scheduler_states=load_lr_scheduler_states,
                load_module_only=load_module_only, data_iter=data_iter)

    def _load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                         load_optimizer_states=True,
                         load_lr_scheduler_states=True,
                         load_module_only=False, data_iter=None):
        if self._ckpt_writer is not None:
            # an in-flight async save must be durable before tags are
            # read — and its failure must surface here, not be read over
            with self._led_attr("checkpoint_load"):
                self._ckpt_writer.drain()
        explicit_tag = tag is not None
        if tag is None:
            latest = os.path.join(load_dir, LATEST_FILE)
            if not os.path.isfile(latest):
                logger.warning(f"no 'latest' file at {latest}; nothing loaded")
                return None, {}
            with open(latest) as f:
                tag = f.read().strip()
        tag = self._verify_load_tag(load_dir, str(tag), explicit_tag)

        from deepspeed_tpu.runtime import checkpoint_io
        import glob as _glob
        path = self._get_ckpt_name(load_dir, tag)
        with self._led_attr("checkpoint_load"), \
                self.telemetry.span("checkpoint/load", tag=str(tag)):
            sd = checkpoint_io.load_file(path, kind="model_states")
            zero_paths = sorted(_glob.glob(os.path.join(
                load_dir, str(tag), "zero_pp_rank_*" + OPTIM_FILE_SUFFIX)))
            zero_payloads = [checkpoint_io.load_file(p, kind="zero_states")
                             for p in zero_paths]
        saved_dp = (zero_payloads[0].get("partition_count")
                    if zero_payloads else None)
        if saved_dp is not None and saved_dp != self.dp_world_size:
            # elastic resize (reference stage_1_and_2.py:2023
            # _restore_from_elastic_fp32_weights / the 'universal
            # checkpoint' load path): shards carry their GLOBAL indices,
            # so restore_tree reassembles the full tree from the saved
            # world size and re-slices it onto the current one — every
            # checkpoint here is 'universal'; load_universal_checkpoint
            # is honored by construction.
            log_dist(
                f"elastic checkpoint load: saved at dp={saved_dp}, "
                f"resuming at dp={self.dp_world_size} (shard reassembly)",
                ranks=[0])
            self._chronicle_emit(
                "elastic_resume", tag=str(tag), saved_dp=int(saved_dp),
                dp=int(self.dp_world_size),
                detail=f"shard reassembly dp={saved_dp}->"
                       f"{self.dp_world_size}")

        if sd.get("module") is not None:
            module_np = sd["module"]
            if sd.get("has_moe_layers"):
                module_np = checkpoint_io.restore_moe_experts(
                    os.path.join(load_dir, str(tag)), module_np,
                    sd.get("moe_layer_prefixes", []),
                    expert_counts=sd.get("moe_expert_counts"))
            params = jax.device_put(module_np, self.param_shardings)
        else:
            # reassemble sharded params from the per-process files
            params = checkpoint_io.restore_tree(
                self.state.params,
                [z["param_shards"] for z in zero_payloads],
                self.param_shardings)
        new_state = self.state._replace(params=params)

        client_state = sd.get("client_state", {})
        if not load_module_only:
            self.global_steps = sd.get("global_steps", 0)
            self.global_samples = sd.get("global_samples", 0)
            self.skipped_steps = sd.get("skipped_steps", 0)
            self.micro_steps = sd.get("micro_steps", 0)
            # state.step counts APPLIED steps only (it indexes the LR
            # schedule), so skipped steps must be subtracted on restore.
            new_state = new_state._replace(
                step=jnp.asarray(self.global_steps - self.skipped_steps,
                                 jnp.int32),
                scale=new_state.scale._replace(
                    loss_scale=jnp.float32(sd.get("loss_scale", 1.0))))
            if load_lr_scheduler_states and self.lr_scheduler is not None \
                    and sd.get("lr_scheduler") is not None:
                self.lr_scheduler.load_state_dict(sd["lr_scheduler"])

            if load_optimizer_states:
                if not zero_payloads:
                    logger.warning(
                        f"no zero_pp_rank files under {load_dir}/{tag}; "
                        f"resuming with FRESH optimizer state and loss scale")
                elif self._offload:
                    # host-optimizer moments are SHARD-LOCAL: restore only
                    # from THIS process's own zero file; another rank's
                    # moments belong to different param slices. Routed
                    # through checkpoint_io.load_file so this read gets
                    # the same span / byte-counter / ledger attribution
                    # as every other checkpoint read (it used to be a
                    # bare open()+pickle.load, invisible to telemetry)
                    own = self._get_zero_ckpt_name(load_dir, tag)
                    if os.path.isfile(own):
                        self._pending_offload_sd = checkpoint_io.load_file(
                            own, kind="zero_states").get(
                                "offload_optimizer_state")
                    else:
                        logger.warning(
                            f"offload moments for this rank missing "
                            f"({own}); resuming with FRESH moments")
                        self._pending_offload_sd = None
                elif zero_payloads[0].get("format") != "shards-v1":
                    # pre-shard-format checkpoint: raw pytree per file
                    opt_state = jax.device_put(
                        jax.tree.map(jnp.asarray,
                                     zero_payloads[0]["optimizer_state_dict"]),
                        self.opt_shardings)
                    new_state = new_state._replace(opt_state=opt_state)
                else:
                    opt_state = checkpoint_io.restore_tree(
                        self.state.opt_state,
                        [z["optimizer_state_dict"] for z in zero_payloads],
                        self.opt_shardings)
                    new_state = new_state._replace(opt_state=opt_state)
                # full dynamic-scaler state so a resumed run is
                # bit-identical to an uninterrupted one (all formats)
                ss = (zero_payloads[0].get("scale_state")
                      if zero_payloads else None)
                if ss is not None:
                    new_state = new_state._replace(
                        scale=LossScaleState(
                            loss_scale=jnp.float32(ss["loss_scale"]),
                            good_steps=jnp.int32(ss["good_steps"]),
                            hysteresis=jnp.int32(ss["hysteresis"])))

            # deterministic data-pipeline resume: rewind the caller's
            # loader to the exact (epoch, batch offset) the save
            # captured — composes with the prefetcher (the skip lives in
            # the index plan) and set_epoch shuffle semantics
            if data_iter is not None:
                it_state = sd.get("data_iterator")
                restore = getattr(data_iter, "load_state_dict", None)
                if it_state is None:
                    logger.warning(
                        "load_checkpoint(data_iter=...): the checkpoint "
                        "carries no data-iterator state (saved without "
                        "data_iter=); the stream is NOT rewound")
                elif restore is None:
                    logger.warning(
                        "load_checkpoint(data_iter=...): the iterator has "
                        "no load_state_dict(); the stream is NOT rewound")
                else:
                    restore(it_state)

        self.state = new_state
        if self._offload:
            # rebuild host masters from the freshly loaded params, then
            # restore the host optimizer moments
            self._offload_opt = self._make_offload_optimizer()
            sd_off = getattr(self, "_pending_offload_sd", None)
            if sd_off is not None:
                self._offload_opt.load_state_dict(sd_off)
                self._pending_offload_sd = None
        log_dist(f"loaded checkpoint {load_dir}/{tag}", ranks=[0])
        # after the counters are restored: the event's step IS the
        # resumed position, which is what a timeline reader wants
        self._chronicle_emit("checkpoint_load", tag=str(tag),
                             dir=load_dir)
        return path, client_state

    # ------------------------------------------------- consolidated exports
    def _consolidated_16bit_state_dict(self):
        """Gathered bit16 copy of the params (reference
        _zero3_consolidated_16bit_state_dict, engine.py:3025)."""
        dtype = (jnp.bfloat16 if self.compute_dtype == jnp.float32
                 else self.compute_dtype)
        fully_addressable = all(
            getattr(x, "is_fully_addressable", True)
            for x in jax.tree.leaves(self.state.params))
        if fully_addressable:
            gathered = jax.device_get(self.state.params)
        else:
            # multi-host ZeRO-3: all-gather across processes first
            from jax.experimental import multihost_utils
            gathered = multihost_utils.process_allgather(self.state.params)
        return jax.tree.map(
            lambda x: np.asarray(x).astype(dtype)
            if np.issubdtype(np.asarray(x).dtype, np.floating) else
            np.asarray(x), gathered)

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.bin"):
        """Reference engine.save_16bit_model (engine.py:3098): one
        consolidated bit16 weight file for HF-style interchange."""
        import deepspeed_tpu.comm as dist
        from deepspeed_tpu.runtime import checkpoint_io
        os.makedirs(save_dir, exist_ok=True)
        if dist.get_rank() == 0:
            with self._led_attr("checkpoint_save"), \
                    self.telemetry.span("checkpoint/save_16bit_model"):
                checkpoint_io.dump_file(
                    self._consolidated_16bit_state_dict(),
                    os.path.join(save_dir, save_filename), kind="bit16")
        return True
