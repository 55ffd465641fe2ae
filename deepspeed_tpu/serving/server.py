"""Serving engine — the synchronous request-level front-end.

``ServingEngine`` glues the subsystem together on top of an
``InferenceEngine`` (which owns params, dtype/int8-weight handling and
the mesh): a ``PagedKVCache`` block pool whose rows are what the model's
configuration says it caches (K and V heads, or one latent a token; for
layers that carry a state, pools of one row a slot beside it), the
``PagedRunner``'s two compiled programs, the FCFS continuous-batching scheduler, and chunked
prefill. The API is deliberately synchronous — ``submit()`` enqueues,
``step()`` advances the world by one scheduler iteration (one bounded
prefill chunk per still-prefilling slot, several slots' chunks to a
prefill dispatch, + one decode dispatch),
``collect()`` drains finished requests — so a caller (or
``serve_forever``) owns the loop and there is no hidden thread to
reason about.

The host runs ONE STEP AHEAD of the device. ``step()`` call k schedules
step k from counts, dispatches its prefill chunks and its decode
program, and only then reads back ("lands") the tokens of step k-1,
which the device finished before it began step k's prefill: when a
decode program ends, the next step is already queued, and the host's
work of a step (scheduling, inputs, delivery, gauges, the caller's
``submit()`` and ``collect()``) happens while the device runs. Step k's
decode takes a slot's input from step k-1's output where it lies, on
the device. Counts (``cached_len``, ``Request.dispatched``, budgets,
block growth) advance at DISPATCH; ``output_tokens``, the EOS test,
``finish``, the prefix index, TTFT and the latency histograms happen at
LANDING, one step later. Where a step must know its tokens it lands
first, read from its own state and never from an option: under
speculation (``accepted`` decides the positions), before an eviction
(``full_prompt`` must hold every token whose KV was written), and on
the way out (``close``, the end of ``serve_forever``,
``profile_window``).

Observability rides the PR-1 registry (so the existing JSONL/Prometheus
sinks carry serving without new plumbing): per-request TTFT and
inter-token latency histograms, queue-depth / active-slot / KV-occupancy
gauges, token/request/preemption counters — and both compiled entry
points are compile-watch wrapped, which is how the tests pin "exactly
one decode program across a heterogeneous trace".
"""

import dataclasses
import os
import time
from typing import List, Optional

import jax
import numpy as np

from deepspeed_tpu.serving.kv_cache import PagedKVCache
from deepspeed_tpu.serving.paged_attention import decode_kernel_runs
from deepspeed_tpu.serving.prefill import ChunkedPrefill
from deepspeed_tpu.serving.runner import (PagedRunner, ServingNotSupported,
                                          cache_layers, cache_rows,
                                          serves_latent, serves_state)
from deepspeed_tpu.serving.sampling import make_rng_lane
from deepspeed_tpu.serving.scheduler import (ContinuousBatchingScheduler,
                                             Request, RequestState)
from deepspeed_tpu.telemetry import chronicle as _chronicle
from deepspeed_tpu.telemetry import metrics as _metrics
from deepspeed_tpu.telemetry.compile_watch import CompileWatch
from deepspeed_tpu.telemetry.serving_observatory import (
    SERVING_HEALTH_SCHEMA, ServingObservatory)
from deepspeed_tpu.telemetry.tracer import trace_span, watch_gc
from deepspeed_tpu.utils.logging import log_dist

# latency histograms: serving cares about the 0.1 ms .. 10 s band
_LAT_BUCKETS = (0.5, 1, 2.5, 5, 10, 25, 50, 100, 250, 500, 1000, 2500,
                5000, 10000)


class ServingLivelockError(RuntimeError):
    """serve_forever made no progress for its hard limit of iterations.

    Carries the full ``serving_report()`` dict in ``.report`` — the
    scheduler/slot/KV state dump and (observability on) the slot-step
    ledger, windows and timelines — so the forensics that motivated the
    guard are captured at the point of death instead of lost with the
    process."""

    def __init__(self, message, report=None):
        super().__init__(message)
        self.report = report


class ServingAdmissionPausedError(RuntimeError):
    """``submit()`` refused a request because the guardian paused
    admission (overload degradation). Carries the SLO rule that
    triggered the pause in ``.rule`` — the structured reason a client
    can act on (back off, shed, retry later)."""

    def __init__(self, rule):
        super().__init__(
            f"admission paused by the guardian (rule {rule!r}): the "
            f"server is shedding load; retry after recovery")
        self.rule = rule


@dataclasses.dataclass
class _Dispatch:
    """One decode dispatch whose tokens the host has not read back."""
    reqs: dict              # slot -> the Request it decoded for
    budget: np.ndarray      # [B] rows each slot was given
    toks: object            # [K, B] device array, its host copy under way
    accepted: object        # [B] device array under speculation, else None
    t0: int                 # perf_counter_ns when the dispatch began
    expert_counts: list     # int32 [4] device arrays: the expert layers'
    #                         counts of the prefill chunks queued before
    #                         this dispatch and, last, of the dispatch
    #                         itself (none without experts)


@dataclasses.dataclass
class RequestOutput:
    req_id: int
    prompt: List[int]
    tokens: List[int]
    finish_reason: str
    ttft_s: Optional[float]
    latency_s: float
    preemptions: int


def _refuse_with_state(config, engine):
    """What a model whose layers carry a per-slot state does not compose
    with, refused by the name of the mechanism."""
    for on, mechanism in (
            (getattr(getattr(config, "prefix_cache", None), "enabled",
                     False),
             "the prefix cache over per-slot state: a state past a block "
             "boundary cannot be shared without a snapshot of it"),
            (getattr(getattr(config, "speculative", None), "enabled",
                     False),
             "speculative decoding over per-slot state: a rejected draft "
             "would need the state rolled back"),
            (engine.quant_scales is not None,
             "int8 weights are not served for a state-space hybrid "
             "(dtype=int8 folds its scales into the GPT-2 block's matmuls "
             "only)")):
        if on:
            raise ServingNotSupported(mechanism)


class ServingEngine:
    def __init__(self, engine, config=None, registry=None,
                 guardian=None, obs_server=None, slo=None,
                 draft_params=None, draft_scales=None):
        """``engine``: an ``InferenceEngine`` wrapping a model the runner
        has a block for (serving/runner.py: the GPT-2 family, latent
        attention with experts); what is not served is refused here,
        by the name of the mechanism, and never mid-step; ``config``: ``DeepSpeedServingConfig``, a ds-config dict
        (with or without the outer ``{"serving": ...}``), or ``None`` for
        defaults; ``guardian``: a :class:`runtime.guardian.Guardian` to
        wire the overload-degradation policy into (falls back to the
        wrapped engine's own, when it has one — training and serving
        actions then share one journal). ``obs_server``/``slo``: the
        mission-control surfaces (telemetry/obs_server.py, telemetry/
        slo.py) — like the guardian they fall back to the wrapped
        engine's own, so an engine armed with ``telemetry.server`` /
        ``telemetry.slo`` config exposes the serving report as a scrape
        route and burns the serving latency objectives automatically.
        ``draft_params``/``draft_scales``: an explicitly configured
        small draft model for ``serving.speculative`` (params pytree,
        pool- and vocab-compatible with the target — see
        serving/speculative.py); ``None`` selects the truncated-layer
        self-draft."""
        from deepspeed_tpu.runtime.config import DeepSpeedServingConfig
        if config is None:
            config = DeepSpeedServingConfig({})
        elif isinstance(config, dict):
            pd = config if "serving" in config else {"serving": config}
            config = DeepSpeedServingConfig(pd)
        self.config = config
        self.engine = engine
        if engine.mp_world_size != 1:
            raise ServingNotSupported(
                "tensor-parallel serving is not supported: the server "
                "drives one chip's decode (mp_size 1; replicas behind "
                f"serving/router.py scale out), got mp_size "
                f"{engine.mp_world_size}")
        model = engine.module
        cfg = model.config
        spec_cfg = getattr(config, "speculative", None)
        if serves_latent(cfg):
            if engine.quant_scales is not None:
                raise ServingNotSupported(
                    "int8 weights are not served for a latent-attention "
                    "model (dtype=int8 folds its scales into the GPT-2 "
                    "block's matmuls only)")
            if spec_cfg is not None and spec_cfg.enabled:
                raise ServingNotSupported(
                    "speculative decoding over a latent cache is not "
                    "served: the draft's layer prefix has no head of its "
                    "own here and a draft module is ROADMAP M7")
        if serves_state(cfg):
            _refuse_with_state(config, engine)
        rows = cache_rows(cfg)      # refuses a model with no served block
        n_pos = int(getattr(cfg, "n_positions"))
        self.max_model_len = (min(int(config.max_model_len), n_pos)
                              if config.max_model_len else n_pos)
        self.max_batch = int(config.max_batch)
        int8_kv = getattr(cfg, "kv_cache_dtype", "auto") == "int8"
        self.max_blocks_per_seq = -(-self.max_model_len
                                    // int(config.block_size))
        num_blocks = int(config.num_blocks) or (
            1 + self.max_batch * self.max_blocks_per_seq)
        layers = cache_layers(cfg)
        self.cache = PagedKVCache(
            n_layer=layers["paged"], block_size=config.block_size,
            num_blocks=num_blocks, dtype=engine.dtype, int8_kv=int8_kv,
            state_layers=layers["per_slot"], slots=self.max_batch, **rows)
        # slot-layer states a decode row moves on (0 without state)
        self._state_layers = layers["per_slot"]
        self.runner = PagedRunner(
            model, self.cache, decode_steps=config.decode_steps)
        # speculative decoding (serving/speculative.py): replaces the
        # decode dispatch with a draft + verify program pair. The
        # scheduler's per-dispatch token budget (and the slot-step
        # ledger's K basis) becomes k+1 — the verify width — so block
        # growth covers every candidate position and the ledger's
        # sums-exact invariant holds on both engines of an A/B.
        self.speculative = None
        self._spec_disabled_rule = None       # None = speculation live
        if spec_cfg is not None and spec_cfg.enabled:
            from deepspeed_tpu.serving.speculative import (
                SpeculativeDecoder, default_draft_layers,
                validate_draft_params)
            draft_layers = spec_cfg.draft_layers or default_draft_layers(
                cfg.n_layer)
            if draft_params is not None:
                validate_draft_params(draft_params, engine.params,
                                      draft_layers)
            self.speculative = SpeculativeDecoder(
                self.runner, k=spec_cfg.k, draft_layers=draft_layers,
                acceptance=spec_cfg.acceptance,
                typical_threshold=spec_cfg.typical_threshold,
                draft_params=draft_params, draft_scales=draft_scales)
        dispatch_tokens = (self.speculative.k + 1
                           if self.speculative is not None
                           else int(config.decode_steps))
        self.scheduler = ContinuousBatchingScheduler(
            self.cache, max_batch=self.max_batch,
            max_model_len=self.max_model_len,
            decode_steps=dispatch_tokens)
        self.registry = registry if registry is not None \
            else _metrics.get_registry()
        # this loop owns the process's garbage collections until close()
        self._gc = watch_gc("serving", self.registry, self)
        # serving observatory (telemetry/serving_observatory.py): pure
        # host bookkeeping — timelines, the slot-step ledger, SLO rules.
        # None when disabled, so every call site is one attribute check.
        obs_cfg = getattr(config, "observability", None)
        self.observatory = None
        if obs_cfg is not None and obs_cfg.enabled:
            self.observatory = ServingObservatory.from_config(
                obs_cfg, max_batch=self.max_batch,
                decode_steps=dispatch_tokens,
                registry=self.registry,
                engine_state_fn=self._engine_state,
                spec_acceptance_floor=(
                    spec_cfg.acceptance_floor
                    if self.speculative is not None else None))
            self.scheduler.observer = self.observatory
        # guardian overload degradation (runtime/guardian.py): the SLO
        # monitor's anomalies feed the guardian, whose serving policy
        # pauses/resumes admission through the callbacks below
        self.guardian = guardian if guardian is not None \
            else getattr(engine, "_guardian", None)
        self._serving_steps = 0
        self._admission_pause_rule = None     # None = admission open
        if self.guardian is not None and self.guardian.enabled \
                and self.guardian.serving_degrade:
            self.guardian.pause_fn = self._pause_admission
            self.guardian.resume_fn = self._resume_admission
            self.guardian.spec_disable_fn = self._disable_speculation
            if self.observatory is not None:
                self.observatory.on_anomaly = self.guardian.hook("serving")
        # mission-control plane (telemetry/obs_server.py + slo.py),
        # shared with the wrapped engine: the serving report becomes one
        # more scrape route, and the serving latency objectives (ttft /
        # e2e percentile targets from the registry histograms) join the
        # burn monitor the training-goodput objective already rides. A
        # page-tier burn (slo_burn_page) lands on the guardian's
        # admission-pause rule list — the SLO monitor closes the loop
        # back to the pause/resume callbacks wired above.
        self._slo = slo if slo is not None else getattr(
            engine, "_slo", None)
        if self._slo is not None and getattr(self._slo, "enabled", False):
            for obj in getattr(self._slo, "serving_defaults", ()):
                self._slo.add_objective(obj)
        self._obs_server = obs_server if obs_server is not None \
            else getattr(engine, "_obs_server", None)
        if self._obs_server is not None:
            self._obs_server.register("serving", self.serving_report)
        # shared-prefix KV reuse (serving.prefix_cache block): the
        # scheduler reads cache.prefix_cache at admission; the server
        # executes the planned COW forks and registers full blocks as
        # prefill/decode completes them
        pc_cfg = getattr(config, "prefix_cache", None)
        if pc_cfg is not None and pc_cfg.enabled:
            self.cache.attach_prefix_cache(
                capacity_blocks=pc_cfg.capacity_blocks)
        # HBM residency observatory (telemetry/memory_observatory.py):
        # shared with the train engine's manager — the serving tick adds
        # THIS server's paged-KV pool to the inventory, so the
        # kv_fragmentation rule reads the allocator's own numbers (the
        # same ones serving_report and the gauges book). None when
        # telemetry.memory is off: one attribute check per step.
        self._memory = getattr(engine, "_memory", None)
        _spp = getattr(engine, "steps_per_print", None)
        self._memory_cadence = (getattr(engine, "_memory_cadence", 0)
                                or (_spp() if callable(_spp) else 0) or 10)
        self._memory_steps = 0
        self._watch = CompileWatch(registry=self.registry)
        self._decode_fn = self._watch.wrap(self.runner.decode_step,
                                           name="serving_decode_step")
        self._prefill_fn = self._watch.wrap(self.runner.prefill_chunk,
                                            name="serving_prefill_chunk")
        # the COW fork's device copy is its OWN compiled program (one
        # signature for the serving lifetime — src/dst are traced
        # scalars), never a third decode/prefill signature
        self._copy_fn = self._watch.wrap(self.runner.copy_block,
                                         name="serving_block_copy")
        # speculative programs: separately named so the acceptance pin
        # "exactly {1 draft, 1 verify}, 0 retraces" reads per-program
        # signature counts, the same discipline as decode/prefill
        self._draft_fn = self._verify_fn = None
        if self.speculative is not None:
            self._draft_fn = self._watch.wrap(
                self.speculative.draft_step, name="serving_draft_step")
            self._verify_fn = self._watch.wrap(
                self.speculative.verify_step, name="serving_verify_step")
        self.prefill = ChunkedPrefill(self._prefill_fn,
                                      chunk_size=config.prefill_chunk,
                                      max_batch=self.max_batch)
        from jax.sharding import NamedSharding, PartitionSpec
        self.pools = self.cache.init_pools(
            NamedSharding(engine.mesh, PartitionSpec()))
        self._next_id = 0
        self._finished = []
        self._lanes = {}              # req_id -> uint32[2] rng lane
        # the decode dispatch whose tokens have not landed (None: none),
        # what a slot did this step (the slot-step ledger's input), and
        # what the first dispatch reads in place of a dispatch before:
        # zeros placed like the program's own output, so that there is
        # one decode program
        self._in_flight = None
        self._landed_pairs = None     # what the last landing counted
        self._acts = {}
        self._no_prev = jax.device_put(
            np.zeros((self.runner.decode_steps, self.max_batch), np.int32),
            NamedSharding(engine.mesh, PartitionSpec()))
        self.scheduler.land_first = self._land
        self.registry.gauge(
            "serving_kv_pool_bytes",
            "allocated paged-KV pool size").set(self.cache.pool_bytes())
        if self._state_layers:
            self.registry.gauge(
                "serving_state_pool_bytes",
                "allocated per-slot state pools (one row a slot and "
                "layer)").set(self.cache.pool_bytes("per_slot"))
        log_dist(
            f"ServingEngine ready: max_batch={self.max_batch} "
            f"block_size={self.cache.block_size} "
            f"blocks={num_blocks} (usable "
            f"{self.cache.allocator.num_usable}) "
            f"max_model_len={self.max_model_len} "
            f"prefill_chunk={self.prefill.chunk_size}"
            f"x{self.prefill.rows} "
            f"kv={'int8' if int8_kv else 'native'}"
            + (f" speculative=k{self.speculative.k}/"
               f"L{self.speculative.draft_layers}"
               f"{'(draft model)' if draft_params is not None else ''}"
               if self.speculative is not None else ""), ranks=[0])

    # ------------------------------------------------------------ submit
    def submit(self, prompt, max_new_tokens=16, temperature=0.0,
               top_p=1.0, seed=0, eos_token_id=None) -> int:
        """Enqueue one request; returns its id. ``temperature<=0`` is
        greedy; otherwise temperature+top-p sampling on the request's own
        seeded RNG lane. Raises :class:`ServingAdmissionPausedError`
        while the guardian has admission paused — failing fast beats
        joining a queue that cannot drain.

        Host work only: nothing is sent to the device and nothing read
        back (the RNG lane is computed on the host,
        ``sampling.make_rng_lane``). The device's queue is a whole step
        deep when a caller submits between steps, and a read-back here
        would wait for all of it."""
        if self._admission_pause_rule is not None:
            self.registry.counter(
                "serving_requests_rejected_total",
                "submits refused while admission was paused",
                labels={"reason": "admission_paused"}).inc()
            self._chronicle_serving("submit_refused", severity="watch",
                                    rule=self._admission_pause_rule)
            raise ServingAdmissionPausedError(self._admission_pause_rule)
        prompt = [int(t) for t in np.asarray(prompt).reshape(-1)]
        vs = self.engine.module.config.vocab_size
        if prompt and (min(prompt) < 0 or max(prompt) >= vs):
            raise ValueError(f"prompt token out of range [0, {vs})")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not 0.0 < top_p <= 1.0:
            # top_p=0 would mask EVERY token (the nucleus keep-mask is
            # exclusive-cumsum < p) and sample token 0 forever; "greedy"
            # is temperature<=0, not top_p=0
            raise ValueError(
                f"top_p must be in (0, 1], got {top_p} (use "
                f"temperature=0 for greedy)")
        req = Request(req_id=self._next_id, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_p=float(top_p),
                      seed=int(seed), eos_token_id=eos_token_id)
        self._next_id += 1
        self.scheduler.submit(req)
        self._lanes[req.req_id] = make_rng_lane(seed)
        if self.observatory is not None:
            self.observatory.record_submit(req)
        self.registry.counter("serving_requests_submitted_total",
                              "requests accepted by submit()").inc()
        self._publish_gauges()
        return req.req_id

    # -------------------------------------------------------------- step
    def step(self) -> bool:
        """One scheduler iteration: admission, one prefill chunk per
        still-prefilling slot (``prefill.rows`` chunks to a dispatch, in
        the plan's order), one decode dispatch, and the landing of
        the decode dispatch of the step BEFORE. Returns True when any
        work was done, so also while tokens are in flight.

        The step is scheduled from counts and dispatched before the
        tokens of the step before it are read back: those land last
        (``_land``), while the device runs what this call queued. A
        token therefore reaches ``output_tokens`` one ``step()`` after
        its dispatch, and a request finishes (and vacates its slot)
        when its last token lands: it is never in neither
        ``scheduler.slots`` nor ``collect()``'s output. A request whose
        every token has been dispatched is not decoded again while the
        last is in flight (one slot-step a request). Under speculation
        a step lands its own tokens, and before an eviction the
        scheduler lands what is in flight (module docstring)."""
        with trace_span("serving_step") as span:
            # slot -> what it did this step (("prefill"|"recompute",
            # n_valid), or ("decode", delivered) for the dispatch that
            # LANDED in it): the slot-step ledger's input; collected
            # DURING the step because finished requests vacate their
            # slots before the step ends
            self._acts = {}
            with trace_span("serving_schedule"):
                plan = self.scheduler.schedule()
                progress = self._drain_failed()
            # COW forks first: a forked request may decode THIS step, and
            # its table already names the fork target — the copy must
            # land before any dispatch reads or writes it
            for req in plan.cow_forks:
                progress |= self._run_cow_fork(req)
            rows = self.prefill.rows
            for i in range(0, len(plan.prefill), rows):
                progress |= self._run_prefill(plan.prefill[i:i + rows])
            ahead = bool(plan.decode_slots) and self._in_flight is not None
            if plan.decode_slots:
                self._run_decode(plan.decode_slots)
                progress = True
            else:
                progress |= self._land()
            span.set(ahead=int(ahead))
            if ahead:
                self.registry.counter(
                    "serving_steps_ahead_total",
                    "steps whose decode was dispatched before the tokens "
                    "of the step before had landed").inc()
            if plan.awaiting:
                self.registry.counter(
                    "serving_slot_steps_awaiting_landing_total",
                    "slot-steps held by a request whose last token was "
                    "in flight: not decoded again, not yet "
                    "vacated").inc(plan.awaiting)
            with trace_span("serving_publish"):
                self._publish(progress)
        return progress

    def _publish(self, progress):
        """The step's book-keeping once its dispatches are out: gauges,
        the observatory's slot-step ledger, the SLO and guardian ticks,
        the memory tick (the ``serving_publish`` span). Host numbers
        only: a device array read here would wait for the step that was
        just queued."""
        self._publish_gauges()
        if self.observatory is not None:
            occupied = {i for i, r in enumerate(self.scheduler.slots)
                        if r is not None}
            self.observatory.end_step(
                self._acts, occupied,
                queue_depth=self.scheduler.num_waiting,
                active=self.scheduler.num_active,
                kv_occupancy=self.cache.allocator.occupancy(),
                kv_fragmentation=self._kv_fragmentation(),
                progress=progress)
        if self.guardian is not None or self._slo is not None:
            # serving's own step clock (NOT training steps): the
            # pause policy fires here, and recovery is measured in
            # quiet serving steps
            self._serving_steps += 1
            if self._slo is not None:
                # burn-rate eval BEFORE the guardian tick so a page
                # fired this step pauses admission this step
                self._slo.tick(step=self._serving_steps)
            if self.guardian is not None:
                self.guardian.serving_tick(self._serving_steps)
        self._memory_tick()

    def _pause_admission(self, rule):
        """Guardian overload action: refuse new submits (fail fast with
        the rule as the structured reason) until recovery. Already-queued
        requests keep draining — the point is to stop the queue growing,
        not to drop accepted work."""
        self._admission_pause_rule = str(rule)
        self.registry.gauge(
            "serving_admission_paused",
            "1 while the guardian has admission paused").set(1)
        # rule rides the event: the correlator's join key back to the
        # SLO anomaly that triggered the pause
        self._chronicle_serving("admission_pause", severity="warning",
                                rule=str(rule))
        log_dist(f"serving: admission PAUSED (rule {rule}); new submits "
                 f"fail fast until recovery", ranks=[0])

    def _resume_admission(self):
        """Guardian recovery action: the overload rules stayed quiet for
        ``resume_clear_steps`` serving steps."""
        self._chronicle_serving("admission_resume", severity="info",
                                rule=self._admission_pause_rule)
        self._admission_pause_rule = None
        self.registry.gauge(
            "serving_admission_paused",
            "1 while the guardian has admission paused").set(0)
        log_dist("serving: admission RESUMED", ranks=[0])

    def _disable_speculation(self, rule):
        """Guardian degradation action (``speculation_waste``): windowed
        acceptance collapsed below the configured floor, so every draft
        dispatch is mostly rejected compute — fall back to the plain
        decode program. One-way for the serving lifetime: acceptance is
        a property of the traffic/draft pairing, and flapping between
        program sets would retrace."""
        if self.speculative is None or self._spec_disabled_rule is not None:
            return
        self._spec_disabled_rule = str(rule)
        # the plain program emits ``decode_steps`` rows a dispatch, not
        # the verify width: budgets, and the counts that now advance at
        # dispatch, follow what it will run
        self.scheduler.decode_steps = self.runner.decode_steps
        self.registry.gauge(
            "serving_speculation_disabled",
            "1 after the guardian disabled speculative decoding").set(1)
        self._chronicle_serving("speculation_disable", severity="warning",
                                rule=str(rule))
        log_dist(f"serving: speculation DISABLED (rule {rule}); decode "
                 f"falls back to the plain program", ranks=[0])

    def _fail_all_pending(self, reason):
        """Fail every waiting AND slotted request with *reason* —
        structured last rites instead of a silent livelock death. Slotted
        requests release their KV blocks through the normal finish path,
        so the pool is clean for a post-mortem restart."""
        self._land("drain")
        count = 0
        waiting, self.scheduler.waiting = \
            list(self.scheduler.waiting), type(self.scheduler.waiting)()
        for req in waiting:
            req.state = RequestState.FINISHED
            req.finish_reason = reason
            req.finish_t = time.perf_counter()
            self._finished.append(req)
            count += 1
        for slot, req in enumerate(self.scheduler.slots):
            if req is None:
                continue
            self.scheduler.finish(req, reason)
            self._finished.append(req)
            if self.observatory is not None:
                self.observatory.record_finish(req, reason, slot)
            count += 1
        if count:
            self.registry.counter(
                "serving_requests_finished_total",
                "requests completed", labels={"reason": reason}).inc(count)
        return count

    def _drain_failed(self) -> bool:
        """Requests the scheduler failed at admission (prompt + generated
        tokens outgrew the pool) finish with reason 'capacity'."""
        failed = self.scheduler.failed
        if not failed:
            return False
        self.scheduler.failed = []
        for req in failed:
            self._finished.append(req)
            self.registry.counter(
                "serving_requests_finished_total",
                "requests completed", labels={"reason": "capacity"}).inc()
        return True

    def _run_cow_fork(self, req) -> bool:
        """Execute one planned copy-on-write fork: device-copy the shared
        source block into the request's private fork target, then release
        the source reference the admission pinned. One compiled program,
        one block of traffic — the whole cost of diverging from a shared
        prefix."""
        src, idx = req.cow_fork
        with trace_span("serving_cow_fork", req=req.req_id):
            with self.engine.mesh:
                self.pools = self._copy_fn(
                    self.pools, np.int32(src),
                    np.int32(req.block_table[idx]))
        self.cache.allocator.free([src], owner=req.req_id)
        req.cow_fork = None
        pc = self.cache.prefix_cache
        if pc is not None:
            pc.cow_forks += 1
        self.registry.counter(
            "serving_prefix_cow_forks_total",
            "copy-on-write block forks (first divergent write to a "
            "shared block)").inc()
        return True

    def _index_blocks(self, req):
        """Register every newly-FULL block of *req* in the prefix index
        (chain digest extended block by block). Called after prefill
        chunks and decode deliveries — generated tokens index too, so a
        follow-up turn carrying this request's output as context hits,
        and a preempted request re-admits onto its own still-cached
        blocks instead of recomputing them."""
        pc = self.cache.prefix_cache
        if pc is None:
            return
        bs = self.cache.block_size
        full = req.full_prompt
        # a block is registered by its tokens: of the positions written
        # (or dispatched), those whose token has LANDED
        n_full = min(min(req.cached_len, len(full) - 1) // bs,
                     len(req.block_table))
        if req.indexed_blocks >= n_full:
            return
        while req.indexed_blocks < n_full:
            b = req.indexed_blocks
            req.prefix_digest = pc.insert(
                req.prefix_digest, full[b * bs:(b + 1) * bs], b * bs,
                req.block_table[b])
            req.indexed_blocks += 1

    def _run_prefill(self, reqs) -> bool:
        """One prefill dispatch: the next chunk of each of ``reqs``, each
        of its own slot, one ``serving_prefill`` span a chunk inside the
        dispatch's span."""
        t0 = time.perf_counter_ns()
        chunks = []
        with trace_span("serving_prefill_dispatch", rows=self.prefill.rows,
                        chunks=len(reqs)):
            for req in reqs:
                # a chunk at position 0 starts its slot's state from zero
                state = ({"state_from_zero": int(req.cached_len == 0)}
                         if self._state_layers else {})
                if state.get("state_from_zero"):
                    self._count_state_resets(1)
                with trace_span("serving_prefill", req=req.req_id,
                                start=req.cached_len, **state) as span:
                    chunk = self.prefill.plan(req)
                    span.set(tokens=chunk.n_valid,
                             recompute=chunk.n_recompute)
                chunks.append(chunk)
            with self.engine.mesh:
                self.pools = self.prefill.dispatch(
                    self.engine.params, self.engine.quant_scales,
                    self.pools, chunks, self.max_blocks_per_seq)
        t1 = time.perf_counter_ns()
        self.registry.counter(
            "serving_prefill_dispatches_total",
            "prefill program calls, each of one or more chunks").inc()
        for chunk in chunks:
            self._book_prefill(chunk, t0, t1)
        return True

    def _book_prefill(self, chunk, t0, t1):
        """A dispatched chunk's counters, slot-step act, prefix index and
        observatory record; a request whose prompt is cached runs."""
        req, n_valid, n_recompute = chunk.req, chunk.n_valid, \
            chunk.n_recompute
        slot = req.slot
        done = self.prefill.remaining(req) == 0
        self.registry.counter("serving_prefill_chunks_total",
                              "prefill chunks executed").inc()
        self.registry.counter("serving_prefill_tokens_total",
                              "prompt tokens cached by prefill").inc(n_valid)
        if n_recompute:
            # preemption COST, not just count: every token here is KV the
            # pool already computed once and an eviction threw away
            self.registry.counter(
                "serving_recompute_tokens_total",
                "tokens re-prefilled because a preemption evicted their "
                "KV").inc(n_recompute)
        # cached_prefill: this chunk exists because the cache DIDN'T
        # cover the whole prompt — the tail of a prefix-hit admission.
        # Still useful work (recompute outranks it: a re-prefilled
        # position is waste whatever got it admitted)
        self._acts[slot] = ("recompute" if n_recompute
                            else ("cached_prefill" if req.prefix_hit_blocks
                                  else "prefill"), n_valid)
        self._index_blocks(req)
        if self.observatory is not None:
            self.observatory.record_prefill(req, slot, chunk.start, n_valid,
                                            n_recompute, t0, t1, done)
        if done:
            req.state = RequestState.RUNNING

    def _decode_inputs(self, decode_slots, prev):
        """The decode program's host-built arguments: the block tables
        and the per-slot vectors, zero (inactive) off ``decode_slots``.
        ``prev`` is the dispatch in flight (or None): a slot it also
        decoded takes its input from that dispatch's tokens, still on
        the device (``prev_row``: the row its budget ended on); a slot
        that has just finished prefill or was re-admitted takes the
        host's ``next_input`` (``prev_row`` -1)."""
        B = self.max_batch
        MB = self.max_blocks_per_seq
        slots = self.scheduler.slots
        bt = self.cache.table_array(
            [r.block_table if r is not None else None for r in slots], MB,
            n_rows=B)
        pos = np.zeros((B,), np.int32)
        active = np.zeros((B,), bool)
        tok = np.zeros((B,), np.int32)
        temp = np.zeros((B,), np.float32)
        top_p = np.ones((B,), np.float32)
        lanes = np.zeros((B, 2), np.uint32)
        budget = np.zeros((B,), np.int32)
        prev_row = np.full((B,), -1, np.int32)
        for i in decode_slots:
            r = slots[i]
            pos[i] = r.cached_len
            active[i] = True
            if prev is not None and prev.reqs.get(i) is r:
                prev_row[i] = prev.budget[i] - 1
            else:
                tok[i] = r.next_input
            temp[i] = r.temperature
            top_p[i] = r.top_p
            lanes[i] = self._lanes[r.req_id]
            budget[i] = r.step_budget
        return bt, pos, active, tok, temp, top_p, lanes, budget, prev_row

    def _paged_block_counts(self, pos, active):
        """KV blocks the active slots hold tokens in, and blocks the
        decode walk fetches for them (paged_attention.py): the kernel
        fetches each slot's own blocks and no other, so the two are
        equal; the jnp loop's trip count is the LONGEST sequence's and
        every trip gathers a block for each of the ``max_batch`` rows.
        The ratio is the share of fetched blocks that hold a live
        token."""
        BS = self.cache.block_size
        blocks = -(-pos[active].astype(np.int64) // BS)
        needed = int(blocks.sum())
        visited = (needed if decode_kernel_runs(self.cache.dtype)
                   else self.max_batch * int(blocks.max(initial=0)))
        self.registry.counter(
            "serving_paged_blocks_needed_total",
            "KV blocks holding the decoding slots' tokens, summed over "
            "decode dispatches").inc(needed)
        self.registry.counter(
            "serving_paged_blocks_visited_total",
            "KV blocks the decode walk fetched (each slot's own in the "
            "kernel, max_batch x the trip count in the jnp loop), summed "
            "over decode dispatches").inc(visited)
        return needed, visited

    def _run_decode(self, decode_slots):
        """Dispatch this step's decode program, then land the tokens of
        the step before, which the device finished before it began this
        step's prefill chunks: the ``serving_decode_wait`` inside this
        span waits for THAT dispatch, never for the one just sent.
        Under speculation ``accepted`` decides the next positions, so
        the step lands its own tokens too, as it always did."""
        with trace_span("serving_decode", batch=len(decode_slots)) as span:
            prev = self._in_flight
            with trace_span("serving_decode_inputs"):
                (bt, pos, active, tok, temp, top_p, lanes, budget,
                 prev_row) = self._decode_inputs(decode_slots, prev)
            needed, visited = self._paged_block_counts(pos, active)
            # the rows asked for and the sum of their input positions
            # (a slot's rows sit at pos, pos + 1, ..., pos + budget - 1)
            rows = budget.astype(np.int64)
            span.set(blocks_needed=needed, blocks_visited=visited,
                     rows=int(rows.sum()),
                     positions=int((rows * pos + rows * (rows - 1) // 2)
                                   .sum()))
            if self._state_layers:
                # slot-layer states the dispatch moves on (a slot past its
                # budget is frozen); a token at position 0 starts from 0
                span.set(state_rows=int(budget.sum()) * self._state_layers)
                self._count_state_resets(int((active & (pos == 0)).sum()))
            spec = (self.speculative
                    if self._spec_disabled_rule is None else None)
            accepted = None
            t0 = time.perf_counter_ns()
            with trace_span("serving_decode_dispatch"), self.engine.mesh:
                if spec is not None:
                    # draft -> verify, device-to-device: the drafted
                    # tokens feed the verify program WITHOUT a host
                    # round-trip, so the pair keeps decode's one-sync-
                    # per-dispatch discipline
                    dparams = (spec.draft_params
                               if spec.draft_params is not None
                               else self.engine.params)
                    dscales = (spec.draft_scales
                               if spec.draft_params is not None
                               else self.engine.quant_scales)
                    self.pools, drafted = self._draft_fn(
                        dparams, dscales, self.pools, bt, pos, active,
                        tok, budget)
                    self.pools, accepted, toks = self._verify_fn(
                        self.engine.params, self.engine.quant_scales,
                        self.pools, bt, pos, active, drafted, tok, temp,
                        top_p, lanes, budget)
                else:
                    self.pools, toks = self._decode_fn(
                        self.engine.params, self.engine.quant_scales,
                        self.pools, bt, pos, active, tok, temp, top_p,
                        lanes, budget,
                        self._no_prev if prev is None else prev.toks,
                        prev_row)
                # the expert layers' counts of this dispatch and of the
                # chunks queued before it land with its tokens
                counts = self.runner.take_expert_counts()
                # the copy to the host starts when the program ends, not
                # when the landing asks for it a step later
                jax.copy_to_host_async((toks, accepted, counts))
            # counts advance at dispatch: the next step is scheduled from
            # them before these tokens are read
            reqs = {i: self.scheduler.slots[i] for i in decode_slots}
            for i, r in reqs.items():
                rows = int(budget[i])
                r.cached_len += rows
                r.in_flight += rows
            self._land()                    # the step before's tokens
            self._in_flight = _Dispatch(reqs, budget, toks, accepted, t0,
                                        counts)
            if spec is not None:
                self._land("speculation")
            if self._landed_pairs is not None:
                held, absent, most, _, decoded, touched = self._landed_pairs
                span.set(pairs_held=held, pairs_absent=absent,
                         pairs_max=most, decode_pairs_held=decoded,
                         experts_touched=touched)

    def _land(self, reason=None) -> bool:
        """Read back the decode dispatch in flight and hand its tokens
        to its requests: ``output_tokens``, ``next_input``, the prefix
        index, the EOS test and ``finish``, TTFT and the latency
        histograms, the observatory's records all happen here. False
        when nothing was in flight. ``reason`` says why a dispatch
        landed before the next step was scheduled (``speculation``,
        ``preemption``: the scheduler's ``land_first``, ``drain``);
        None is the run-ahead order, the landing that follows the next
        step's dispatch. This is the step's one device-to-host read."""
        flight, self._in_flight = self._in_flight, None
        self._landed_pairs = None
        if flight is None:
            return False
        with trace_span("serving_decode_wait"):
            accepted = (None if flight.accepted is None
                        else np.asarray(flight.accepted))    # [B]
            toks = np.asarray(flight.toks)     # [K, B]; the one host sync
            counts = [np.asarray(c) for c in flight.expert_counts]
        t1 = time.perf_counter_ns()
        if counts:
            self._count_expert_pairs(np.sum(counts, axis=0), counts[-1])
        with trace_span("serving_deliver"):
            self._deliver_decoded(flight, toks, accepted, t1)
        if reason is not None:
            self.registry.counter(
                "serving_steps_landed_first_total",
                "decode dispatches landed before the next step was "
                "scheduled, by what made the step need its tokens",
                labels={"reason": reason}).inc()
        return True

    def _count_state_resets(self, n):
        self.registry.counter(
            "serving_state_resets_total",
            "prefill chunks and decode rows that start a slot's per-slot "
            "state from zero (a request's first token)").inc(n)

    def _count_expert_pairs(self, counts, decoded):
        """Book what the expert layers of the landed dispatches counted
        (moe/held_experts.py): token-expert choices whose expert is held
        here and not, the most pairs that one held expert got in a
        dispatch and layer, and the held experts with a pair in one,
        summed; and of the decode dispatch alone (``decoded``, the last
        of them) its pairs held and its experts with a pair, which its
        grouped products read the weights of."""
        held, absent, most, touched = (int(n) for n in counts)
        self._landed_pairs = (held, absent, most, touched,
                              int(decoded[0]), int(decoded[3]))
        for name, n, what in (
                ("serving_moe_pairs_held_total", held,
                 "token-expert choices whose expert this chip holds"),
                ("serving_moe_pairs_absent_total", absent,
                 "token-expert choices whose expert another chip holds: "
                 "left out of the partial sum"),
                ("serving_moe_expert_load_max_total", most,
                 "the most pairs any one held expert got, summed over "
                 "dispatches and expert layers"),
                ("serving_moe_experts_touched_total", touched,
                 "held experts with at least one pair, summed over "
                 "dispatches and expert layers")):
            self.registry.counter(name, what).inc(n)

    def _deliver_decoded(self, flight, toks, accepted, t1):
        """Hand one decode dispatch's tokens to its requests;
        ``accepted`` is the verify program's per-slot count under
        speculation, else None."""
        spec = self.speculative if accepted is not None else None
        now = time.perf_counter()
        self.registry.counter("serving_decode_steps_total",
                              "compiled decode dispatches executed").inc()
        # a request that the landing before this one finished on an EOS
        # had been dispatched once more by then: those rows are dropped
        # (their KV write lay in the request's own block)
        live = {i: r for i, r in flight.reqs.items()
                if r.state is not RequestState.FINISHED}
        overrun = sum(int(flight.budget[i]) for i in flight.reqs
                      if i not in live)
        if self.observatory is not None:
            # before delivery, so each timeline's decode_begin precedes
            # its first_token
            self.observatory.record_decode(
                {i: (r, int(flight.budget[i])) for i, r in live.items()},
                flight.t0, t1)
        # speculative delivery: per slot, min(accepted+1, budget) tokens
        # are real (accepted drafts + the target's bonus token); the
        # rest ROLL BACK by not advancing cached_len past them — the
        # stale pool bytes past the accepted point are masked by
        # past_lens and overwritten by the next dispatch.
        # drafted_rejected books the rejection cost into the slot-step
        # ledger.
        drafted_t = accepted_t = rejected_t = 0
        for i, r in live.items():
            b = int(flight.budget[i])
            cap = b if spec is None else min(int(accepted[i]) + 1, b)
            delivered = self._deliver(r, toks[:cap, i].tolist(), b, now)
            overrun += cap - delivered
            if spec is None:
                self._acts[i] = ("decode", delivered)
                continue
            considered = min(spec.k, max(b - 1, 0))
            rejected = considered - (cap - 1)
            r.spec_drafted += considered
            r.spec_accepted += cap - 1
            drafted_t += considered
            accepted_t += cap - 1
            rejected_t += rejected
            self._acts[i] = ("decode", delivered, rejected)
        if overrun:
            self.registry.counter(
                "serving_decode_overrun_tokens_total",
                "decode rows spent past an EOS: sampled after the token "
                "that ended their request, dropped at landing").inc(overrun)
        if drafted_t:
            self.registry.counter(
                "serving_spec_drafted_total",
                "draft tokens proposed to the verify program").inc(
                    drafted_t)
            self.registry.counter(
                "serving_spec_accepted_total",
                "draft tokens the target accepted").inc(accepted_t)
            if rejected_t:
                self.registry.counter(
                    "serving_spec_rejected_total",
                    "draft tokens the target rejected (rolled back as "
                    "a position edit)").inc(rejected_t)
            drafted_c = self.registry.counter(
                "serving_spec_drafted_total",
                "draft tokens proposed to the verify program").value
            accepted_c = self.registry.counter(
                "serving_spec_accepted_total",
                "draft tokens the target accepted").value
            self.registry.gauge(
                "serving_spec_acceptance_rate",
                "cumulative accepted/drafted ratio of speculative "
                "decoding").set(
                    accepted_c / drafted_c if drafted_c else 0.0)

    def _deliver(self, req, tokens, budget, now):
        """Land one dispatch's tokens on the request (one token in
        single-step mode, up to ``decode_steps`` otherwise; anything the
        request samples past eos/max_tokens is discarded). ``budget`` is
        what the dispatch advanced ``cached_len`` and ``in_flight`` by;
        the rows that do not land (rejected drafts, rows past an EOS)
        roll back as a position edit. Returns the KEPT token count —
        the slot-step ledger's ``decode_useful``."""
        slot = req.slot
        prev = req.last_token_t if req.first_token_t is not None else None
        # KV positions whose tokens had landed before this dispatch (a
        # later dispatch may be in flight already)
        base = req.cached_len - req.in_flight
        delivered = 0
        reason = None
        for token in tokens:
            delivered += 1
            req.output_tokens.append(token)
            req.next_input = token
            if req.eos_token_id is not None and token == req.eos_token_id:
                reason = "eos"
            elif len(req.output_tokens) >= req.max_new_tokens:
                reason = "max_tokens"
            elif base + delivered >= self.max_model_len:
                reason = "model_len"
            if reason is not None:
                break
        req.in_flight -= budget
        req.cached_len -= budget - delivered
        if not delivered:
            return 0
        # register newly-full blocks BEFORE any finish releases the
        # table: a finished request's prefix stays warm in the index
        self._index_blocks(req)
        req.last_token_t = now
        if req.first_token_t is None:
            req.first_token_t = now
            ttft_ms = (now - req.submit_t) * 1e3
            self.registry.histogram(
                "serving_ttft_ms", "submit -> first generated token",
                buckets=_LAT_BUCKETS).observe(ttft_ms)
            if self.observatory is not None:
                self.observatory.record_first_token(req, ttft_ms)
            extra = 0      # same-dispatch tokens are part of the TTFT
        else:
            extra = delivered
        if extra > 0:
            # multi-step dispatches deliver K tokens at once; record the
            # amortised per-token interval so the histogram stays
            # comparable across decode_steps settings
            per_tok = (now - prev) / extra * 1e3
            h = self.registry.histogram(
                "serving_token_latency_ms",
                "inter-token latency per request (dispatch-amortised)",
                buckets=_LAT_BUCKETS)
            for _ in range(extra):
                h.observe(per_tok)
        self.registry.counter(
            "serving_tokens_generated_total",
            "tokens sampled across all requests").inc(delivered)
        if reason is not None:
            self.scheduler.finish(req, reason)
            self._finished.append(req)
            if self.observatory is not None:
                self.observatory.record_finish(req, reason, slot)
            self.registry.counter(
                "serving_requests_finished_total",
                "requests completed", labels={"reason": reason}).inc()
            self.registry.histogram(
                "serving_e2e_latency_ms", "submit -> finish",
                buckets=_LAT_BUCKETS).observe(
                    (req.finish_t - req.submit_t) * 1e3)
        return delivered

    def _publish_gauges(self):
        self.registry.gauge("serving_queue_depth",
                            "requests waiting for admission").set(
                                self.scheduler.num_waiting)
        self.registry.gauge("serving_active_requests",
                            "requests occupying batch slots").set(
                                self.scheduler.num_active)
        self.registry.gauge("serving_kv_occupancy",
                            "fraction of usable KV blocks allocated").set(
                                self.cache.allocator.occupancy())
        self.registry.gauge("serving_kv_free_blocks",
                            "usable KV blocks currently free").set(
                                self.cache.allocator.num_free)
        if self.observatory is not None:
            self.registry.gauge(
                "serving_kv_fragmentation",
                "fraction of allocated KV positions no token has been "
                "written to (block-granularity over-allocation)").set(
                    self._kv_fragmentation())
        pc = self.cache.prefix_cache
        if pc is not None:
            for name, help_, total in (
                    ("serving_prefix_cache_hits_total",
                     "full prompt blocks served read-only from the "
                     "prefix index at admission", pc.hits),
                    ("serving_prefix_cache_misses_total",
                     "full prompt blocks the prefix index did not hold "
                     "at admission", pc.misses)):
                c = self.registry.counter(name, help_)
                delta = total - c.value
                if delta > 0:
                    c.inc(delta)
            self.registry.gauge(
                "serving_prefix_blocks_shared",
                "resident prefix-index blocks currently mapped by at "
                "least one live request").set(pc.shared_blocks())
        for reason, total in self.scheduler.preemptions_by_reason.items():
            # labeled by WHY the eviction happened (capacity_growth: a
            # running slot needed a block and the pool was dry; admission
            # is reserved for a future evict-to-admit policy), so the
            # sinks carry preemption cause — recompute cost rides
            # serving_recompute_tokens_total
            pre = self.registry.counter(
                "serving_preemptions_total",
                "evictions under block pressure, by reason",
                labels={"reason": reason})
            delta = total - pre.value
            if delta > 0:
                pre.inc(delta)
                self._chronicle_serving("preemption", severity="watch",
                                        reason=reason, count=delta)

    # ----------------------------------------------------------- collect
    def collect(self) -> List[RequestOutput]:
        """Drain finished requests (in finish order). A request finishes
        when its last token LANDS, one ``step()`` after the step that
        dispatched it; until then it is in ``scheduler.slots``, so a
        request is always in one of the two."""
        out = []
        for req in self._finished:
            self._lanes.pop(req.req_id, None)
            out.append(RequestOutput(
                req_id=req.req_id, prompt=list(req.prompt),
                tokens=list(req.output_tokens),
                finish_reason=req.finish_reason,
                ttft_s=(None if req.first_token_t is None
                        else req.first_token_t - req.submit_t),
                latency_s=req.finish_t - req.submit_t,
                preemptions=req.preemptions))
        self._finished = []
        return out

    # -------------------------------------------------------------- loop
    def serve_forever(self, request_source=None, max_steps=None):
        """Drive the loop until drained: optionally pull submit-kwargs
        dicts from ``request_source`` (an iterable) to keep the queue
        primed, step until no work remains, return collected outputs.

        A request holds its slot until its last token has landed, so
        "no work remains" means nothing is in flight either. A loop cut
        by ``max_steps`` lands what its last step left in flight before
        it returns: the outputs and ``scheduler.slots`` then hold every
        token that was dispatched."""
        source = iter(request_source) if request_source is not None else None
        outputs = []
        steps = 0
        idle = 0
        while True:
            while source is not None and \
                    self._admission_pause_rule is None and \
                    self.scheduler.num_waiting < 2 * self.max_batch:
                try:
                    self.submit(**next(source))
                except StopIteration:
                    source = None
                    break
            if not self.scheduler.has_work() and source is None:
                break
            idle = idle + 1 if not self.step() else 0
            if idle > 1000:
                # the scheduler guarantees forward progress (budget
                # shrink-to-owned-capacity + admission-infeasibility
                # failure); a long idle spin means that invariant broke.
                # Last rites BEFORE raising: every pending request fails
                # with a structured reason (a client sees "livelock", not
                # a hang), and the forensics snapshot is forced to disk —
                # then the report also rides the exception.
                n = self._fail_all_pending("livelock")
                self._chronicle_serving(
                    "livelock", severity="critical", failed=n,
                    detail=f"no progress for 1000 iterations; failed {n} "
                           f"pending request(s)")
                report = self.serving_report(write=True)
                raise ServingLivelockError(
                    "serving made no progress for 1000 iterations — "
                    f"failed {n} pending request(s) with reason "
                    f"'livelock'; "
                    f"kv_free={self.cache.allocator.num_free}/"
                    f"{self.cache.allocator.num_usable} blocks "
                    "(scheduler/slot/KV state dump attached as "
                    ".report)", report=report)
            outputs.extend(self.collect())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        if self._land("drain"):
            outputs.extend(self.collect())
        return outputs

    # ------------------------------------------- HBM residency observatory
    def _memory_tick(self, force=False):
        """Serving-side residency window at the memory cadence: the
        train-engine inventory plus this server's paged-KV pool, so the
        observatory attributes the pool as ``kv_pool`` and its
        ``kv_fragmentation`` rule judges the allocator's own numbers —
        the same ones ``serving_report()`` and the gauges book. A host
        RPC into the runtime's allocator bookkeeping only; never a
        device sync, never a new decode/prefill signature."""
        mon = self._memory
        if mon is None:
            return None
        self._memory_steps += 1
        if not force and self._memory_steps % self._memory_cadence != 0:
            return None
        self.engine._memory_arm(mon)
        try:
            from deepspeed_tpu.telemetry import memory_observatory as _mo
            from deepspeed_tpu.telemetry import pprof as _pprof
            sample = _mo.profile_sample(
                _pprof.fetch_device_memory_profile())
        except Exception as e:
            if not self.engine._memory_warned_fetch:
                self.engine._memory_warned_fetch = True
                log_dist(
                    f"[memory] device memory profile unavailable on this "
                    f"backend: {e} — serving residency windows disabled",
                    ranks=[0])
            return None
        inv = self.engine._memory_build_inventory()
        totals = dict(inv["totals"])
        totals["kv_pool"] = self.cache.pool_bytes()
        alloc = self.cache.allocator
        sample["step"] = self._memory_steps
        sample["inventory"] = totals
        sample["param_buckets"] = inv["param_buckets"]
        sample["opt_buckets"] = inv["opt_buckets"]
        sample["kv"] = {
            "pool_bytes": self.cache.pool_bytes(),
            "block_size": self.cache.block_size,
            "free_blocks": alloc.num_free,
            "usable_blocks": alloc.num_usable,
            "occupancy": round(alloc.occupancy(), 4),
            "fragmentation": round(self._kv_fragmentation(), 4),
        }
        mon.observe(sample)
        return sample

    def memory_report(self, write=False):
        """The serving-side residency report: forces one window (with
        the KV pool in the inventory) and returns the monitor's report;
        ``write=True`` also writes MEMORY_ANATOMY.json.
        ``{"enabled": False}`` when ``telemetry.memory`` is off."""
        mon = self._memory
        if mon is None:
            return {"enabled": False}
        self._memory_tick(force=True)
        if write:
            mon.write_report()
        return mon.report()

    # -------------------------------------------------------- inspection
    def _kv_fragmentation(self):
        """Internal fragmentation of the live block tables: the fraction
        of allocated KV positions no token has been written to (block
        granularity over-allocation). 0.0 with nothing allocated."""
        allocated = used = 0
        for r in self.scheduler.slots:
            if r is not None:
                allocated += len(r.block_table) * self.cache.block_size
                used += r.cached_len
        return (1.0 - used / allocated) if allocated else 0.0

    def _engine_state(self):
        """Host-side scheduler/slot/KV dump — the forensics core of
        ``serving_report()`` and the livelock exception."""
        slots = []
        for r in self.scheduler.slots:
            slots.append(None if r is None else {
                "req_id": r.req_id,
                "state": r.state.value,
                "prompt_len": len(r.prompt),
                "generated": len(r.output_tokens),
                "in_flight": r.in_flight,
                "cached_len": r.cached_len,
                "blocks": len(r.block_table),
                "step_budget": r.step_budget,
                "preemptions": r.preemptions,
            })
        alloc = self.cache.allocator
        return {
            "scheduler": {
                "waiting": self.scheduler.num_waiting,
                "active": self.scheduler.num_active,
                "waiting_req_ids": [r.req_id for r in
                                    list(self.scheduler.waiting)[:32]],
                "slots": slots,
                "preemptions_by_reason":
                    dict(self.scheduler.preemptions_by_reason),
            },
            "kv": {
                "block_size": self.cache.block_size,
                "num_blocks": alloc.num_blocks,
                "usable": alloc.num_usable,
                "free": alloc.num_free,
                "allocated": alloc.num_allocated,
                "occupancy": round(alloc.occupancy(), 4),
                "fragmentation": round(self._kv_fragmentation(), 4),
                "pool_bytes": self.cache.pool_bytes(),
            },
            "prefix_cache": (None if self.cache.prefix_cache is None
                             else self.cache.prefix_cache.stats()),
            "compile": self.compile_stats(),
        }

    def router_signals(self):
        """The per-replica admission signals a :class:`ServingRouter`
        scores: queue/occupancy pressure plus whether the PR-9 SLO rules
        fired RECENTLY (within the last two observation windows —
        treating an incident from an hour ago as live would park a
        healthy replica forever). With observability off the SLO flags
        stay False and routing degrades to load + affinity."""
        sig = {
            "queue_depth": self.scheduler.num_waiting,
            "active": self.scheduler.num_active,
            "kv_occupancy": self.cache.allocator.occupancy(),
            "ttft_slo_breach": False,
            "queue_growth": False,
        }
        obs = self.observatory
        if obs is not None:
            horizon = obs.steps_seen - 2 * obs.window
            for a in obs.anomalies:
                if a.get("step", 0) >= horizon and \
                        a.get("rule") in ("ttft_slo_breach",
                                          "queue_growth"):
                    sig[a["rule"]] = True
        return sig

    def _chronicle_serving(self, event, severity=None, detail=None,
                           **data):
        """Serving event into the run chronicle (admission pause/resume,
        preemption, livelock last rites). ``step`` is the SERVING step
        clock, not the train step — readers disambiguate by the event's
        ``source``."""
        chron = _chronicle.get_chronicle()
        if chron.enabled:
            chron.emit("serving", source="serving",
                       step=self._serving_steps, severity=severity,
                       detail=detail, event=event, **data)

    def chronicle_report(self, write=False):
        """Serving counterpart of ``engine.chronicle_report``: the
        chronicle is process-global and armed by the engine that owns
        it, so this delegates to the wrapped engine (the serving events
        above are already in the same timeline).
        ``{"enabled": False}`` when no chronicle is armed."""
        fn = getattr(self.engine, "chronicle_report", None)
        if fn is not None:
            return fn(write=write)
        return {"enabled": False}

    def serving_report(self, write=False):
        """The structured serving forensics dict: the observatory report
        (slot-step ledger, windows, SLO anomalies, per-request
        timelines) plus the live scheduler/slot/KV dump under
        ``engine_state``. With observability disabled the engine-state
        dump is still returned — the livelock guard needs it either way.
        ``write=True`` also snapshots it to the observatory's
        ``SERVING_HEALTH.json`` path (observability on only)."""
        if self.observatory is not None:
            report = self.observatory.report()
            if write:
                self.observatory.write_snapshot(report=report, force=True)
            return report
        return {"schema": SERVING_HEALTH_SCHEMA, "enabled": False,
                "engine_state": self._engine_state()}

    def profile_window(self, steps=3, out=None, write=True):
        """Measured device-time anatomy for *steps* scheduler
        iterations — the serving analog of ``engine.profile_step``.

        Runs a bounded ``jax.profiler`` capture around N annotated
        ``step()`` calls (landing each step's tokens and blocking on the
        KV pools inside its annotation, so that a step's device work and
        its delivery lie in its own mark), post-processes the
        trace with the xplane parser and writes the schema-pinned
        report (default ``telemetry/STEP_ANATOMY.serving.json``).
        Inert (``{"enabled": False}``) when the profiler is
        unavailable or ``DS_TELEMETRY_ANATOMY=0``."""
        from deepspeed_tpu.telemetry import step_anatomy
        from deepspeed_tpu.telemetry.ledger import (
            profiler_available, _start_trace, _stop_trace)
        env = os.environ.get("DS_TELEMETRY_ANATOMY")
        if env is not None and env.lower() not in ("1", "true", "yes",
                                                   "on"):
            return {"enabled": False,
                    "reason": "DS_TELEMETRY_ANATOMY disabled"}
        if not profiler_available():
            return {"enabled": False,
                    "reason": "jax.profiler programmatic capture "
                              "unavailable"}
        outdir = os.path.dirname(out) if out else "telemetry/"
        trace_dir = os.path.join(outdir or ".", "anatomy_profile_serving")
        os.makedirs(trace_dir, exist_ok=True)
        try:
            _start_trace(trace_dir)
        except Exception as e:
            return {"enabled": False,
                    "reason": f"profiler start_trace failed: {e}"}
        try:
            from jax.profiler import TraceAnnotation
            for i in range(int(steps)):
                with TraceAnnotation(step_anatomy.STEP_MARK, step=i):
                    self.step()
                    self._land("drain")
                    jax.block_until_ready(self.pools)
        finally:
            try:
                _stop_trace()
            except Exception:
                pass
        report = step_anatomy.summarize_capture(trace_dir)
        if report is None:
            return {"enabled": False,
                    "reason": f"profiler wrote no .xplane.pb under "
                              f"{trace_dir}"}
        report["enabled"] = True
        report.setdefault("source", {})["surface"] = "serving"
        if write:
            path = out or os.path.join(
                outdir or ".", "STEP_ANATOMY.serving.json")
            step_anatomy.write_report(report, path)
            report["report_path"] = path
        return report

    def close(self):
        """Teardown: force the observatory's final forensics snapshot.
        Anomalies whose only firings landed inside the 5 s snapshot
        throttle window would otherwise exit the process unexplained —
        ``close()`` is what guarantees the last incident reaches
        ``SERVING_HEALTH.json``. The obs-server scrape route is
        unregistered first — its report provider points at this
        object. Tokens still in flight land first, so the snapshot and
        a last ``collect()`` hold them."""
        self._land("drain")
        self._gc.close()
        if self._obs_server is not None:
            self._obs_server.unregister("serving")
            self._obs_server = None
        if self.observatory is not None:
            self.observatory.close()

    def compile_stats(self):
        """Signature counts per compiled entry point (the 'one decode
        program' acceptance guard reads this)."""
        per_fn = self._watch._per_fn
        stats = {
            "decode_signatures": len(
                per_fn.get("serving_decode_step", {}).get("sigs", ())),
            "prefill_signatures": len(
                per_fn.get("serving_prefill_chunk", {}).get("sigs", ())),
            "retraces": self._watch.retraces,
        }
        if self.speculative is not None:
            # only present with speculation configured, so the exact
            # dict pins on the non-speculative arms stay exact
            stats["draft_signatures"] = len(
                per_fn.get("serving_draft_step", {}).get("sigs", ()))
            stats["verify_signatures"] = len(
                per_fn.get("serving_verify_step", {}).get("sigs", ()))
        return stats
