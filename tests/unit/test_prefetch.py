"""Asynchronous input pipeline (runtime/prefetch.py + engine glue).

Covers the hard edges the tentpole promises: depth semantics (never more
than ``depth`` batches materialized), worker-exception re-raise at the
consumer's ``next()``, leak-free shutdown, batch order/values identical
to the unprefetched loader (including RepeatingLoader epoch advance
across wrap-around), the multi-process device-stage guard — and the
acceptance e2e: against an artificially slow loader, prefetch-enabled
``train_batch`` is materially faster per step and the goodput ledger's
``input_wait`` fraction collapses (the PR-4 ``input_stall`` rule no
longer fires).
"""

import threading
import time

import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.simple import (SimpleModel, random_dataset,
                                         sample_batch)
from deepspeed_tpu.runtime.dataloader import (DeepSpeedDataLoader,
                                              RepeatingLoader)
from deepspeed_tpu.runtime.prefetch import PrefetchIterator, PrefetchLoader

HIDDEN = 32


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("ds-prefetch")]


def _assert_no_threads(timeout=3.0):
    """The pipeline threads poll at 0.2 s; give them a moment to drain."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if not _prefetch_threads():
            return
        time.sleep(0.05)
    raise AssertionError(f"leaked prefetch threads: "
                         f"{[t.name for t in _prefetch_threads()]}")


@pytest.fixture(autouse=True)
def _no_thread_leaks():
    yield
    _assert_no_threads()


def _int_loader(n=32, batch_size=4, **kw):
    return DeepSpeedDataLoader(list(range(n)), batch_size=batch_size, **kw)


# ------------------------------------------------------- order and values

class TestOrderAndValues:
    @pytest.mark.parametrize("workers", [1, 2, 4])
    def test_identical_to_unwrapped(self, workers):
        base = [np.asarray(b).tolist()
                for b in _int_loader(shuffle=True, seed=3)]
        pl = PrefetchLoader(_int_loader(shuffle=True, seed=3), depth=2,
                            num_workers=workers)
        with pl:
            got = [np.asarray(b).tolist() for b in pl]
        assert got == base

    def test_repeating_loader_epoch_advance_across_wraparound(self):
        """set_epoch must fire between epochs IN ORDER: the prefetched
        stream's epoch-2 batches use epoch 2's permutation, exactly like
        the unprefetched RepeatingLoader."""
        def epochs(loader):
            rl = RepeatingLoader(loader)
            n = 8
            return ([np.asarray(next(rl)).tolist() for _ in range(n)],
                    [np.asarray(next(rl)).tolist() for _ in range(n)])

        base1, base2 = epochs(_int_loader(shuffle=True, seed=0))
        pl = PrefetchLoader(_int_loader(shuffle=True, seed=0), depth=3,
                            num_workers=2)
        with pl:
            got1, got2 = epochs(pl)
        assert (got1, got2) == (base1, base2)
        assert base1 != base2          # the epoch really advanced
        assert pl.epoch == 1

    def test_finite_iteration_stops_cleanly(self):
        pl = PrefetchLoader(_int_loader(), depth=2)
        it = iter(pl)
        batches = list(it)
        assert len(batches) == 8
        with pytest.raises(StopIteration):
            next(it)
        with pytest.raises(StopIteration):   # stays exhausted
            next(it)

    def test_len_and_set_epoch_delegate(self):
        inner = _int_loader(shuffle=True)
        pl = PrefetchLoader(inner, depth=2)
        assert len(pl) == len(inner)
        pl.set_epoch(5)
        assert inner.epoch == 5
        assert pl.epoch == 5


# ----------------------------------------------------------------- depth

class _CountingDataset:
    """dataset[i] == i, counting materializations."""

    def __init__(self, n):
        self.n = n
        self.calls = 0
        self._lock = threading.Lock()

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        with self._lock:
            self.calls += 1
        return i


class TestDepthSemantics:
    @pytest.mark.parametrize("depth,workers", [(1, 1), (2, 2), (3, 2)])
    def test_never_more_than_depth_materialized(self, depth, workers):
        bs = 4
        ds = _CountingDataset(64)
        pl = PrefetchLoader(
            DeepSpeedDataLoader(ds, batch_size=bs), depth=depth,
            num_workers=workers)
        with pl:
            it = iter(pl)
            consumed = 0
            for _ in range(3):
                next(it)
                consumed += 1
                time.sleep(0.3)         # let the pipeline run ahead
                # materialized-or-in-flight is gated at `depth` beyond
                # what the consumer already took
                assert ds.calls <= (consumed + depth) * bs, (
                    f"pipeline ran {ds.calls // bs} batches ahead of "
                    f"{consumed} consumed at depth={depth}")


# ------------------------------------------------------------- exceptions

class _Boom(RuntimeError):
    pass


class TestExceptionPropagation:
    def test_generic_iterator_error_reraised_in_sequence(self):
        def gen():
            yield 1
            yield 2
            raise _Boom("worker died")

        it = PrefetchIterator(gen(), depth=2)
        assert next(it) == 1
        assert next(it) == 2
        with pytest.raises(_Boom, match="worker died"):
            next(it)
        with pytest.raises(_Boom):      # a failed pipeline stays failed
            next(it)

    def test_indexed_worker_error_reraised_in_sequence(self):
        class PoisonDataset(_CountingDataset):
            def __getitem__(self, i):
                if i == 9:              # poisons batch 2 (bs=4)
                    raise _Boom("bad sample")
                return super().__getitem__(i)

        pl = PrefetchLoader(
            DeepSpeedDataLoader(PoisonDataset(32), batch_size=4),
            depth=2, num_workers=2)
        with pl:
            it = iter(pl)
            assert np.asarray(next(it)).tolist() == [0, 1, 2, 3]
            assert np.asarray(next(it)).tolist() == [4, 5, 6, 7]
            with pytest.raises(_Boom, match="bad sample"):
                next(it)

    def test_place_fn_error_propagates(self):
        it = PrefetchIterator(iter([1, 2]), depth=2,
                              place_fn=lambda b: 1 / 0)
        with pytest.raises(ZeroDivisionError):
            next(it)


# --------------------------------------------------------------- shutdown

class TestShutdown:
    def test_close_joins_threads_mid_stream(self):
        pl = PrefetchLoader(_int_loader(n=1024, batch_size=4), depth=2,
                            num_workers=2)
        it = iter(pl)
        next(it)
        assert _prefetch_threads()      # pipeline is live
        pl.close()
        _assert_no_threads()

    def test_close_is_idempotent_and_iterator_is_ctx_manager(self):
        with PrefetchIterator(iter([1, 2, 3]), depth=2) as it:
            assert next(it) == 1
        it.close()
        it.close()

    def test_exhaustion_self_closes(self):
        list(iter(PrefetchLoader(_int_loader(), depth=2)))
        _assert_no_threads()

    def test_close_with_device_stage_and_pending_slots_does_not_hang(self):
        """Review regressions: (1) close() leaves queued slots no worker
        will ever fill — the device thread must not block forever in an
        untimed slot wait; (2) with the device stage armed, a consumer
        blocked in the OUTPUT queue must be woken by close() (the hostq
        sentinel stops at the device thread)."""
        bs = 4

        def slow_collate(samples):
            time.sleep(0.25)
            import numpy as _np
            return _np.stack([_np.asarray(s) for s in samples])

        pl = PrefetchLoader(
            DeepSpeedDataLoader(list(range(256)), batch_size=bs,
                                collate_fn=slow_collate),
            depth=4, num_workers=2, place_fn=lambda b: b)
        it = iter(pl)
        got = []

        def consume():
            try:
                while True:
                    got.append(next(it))
            except StopIteration:
                got.append("stopped")

        consumer = threading.Thread(target=consume, daemon=True)
        consumer.start()
        time.sleep(0.1)               # pipeline live, slots in flight
        t0 = time.monotonic()
        pl.close()
        assert time.monotonic() - t0 < 3.0, "close() blocked on a slot"
        consumer.join(timeout=3.0)
        assert not consumer.is_alive(), \
            "consumer was never woken by close()"
        assert got and got[-1] == "stopped"
        _assert_no_threads()

    def test_abandoned_iterator_is_reclaimed_by_gc(self):
        """Breaking out of an epoch mid-stream and dropping the iterator
        must not leak the pipeline: threads hold only the shared state,
        so GC collects the iterator and its finalizer stops them
        (review regression — an atexit strong ref used to pin it)."""
        import gc
        pl = PrefetchLoader(_int_loader(n=1024, batch_size=4), depth=2,
                            num_workers=2)
        it = iter(pl)
        next(it)
        assert _prefetch_threads()
        del it
        pl._iters = []                # drop the loader's weakref too
        gc.collect()
        _assert_no_threads()

    def test_close_with_blocked_filler_does_not_hang(self):
        # depth=1 and nothing consumed: the filler is parked on the
        # depth semaphore; close() must still return promptly
        pl = PrefetchLoader(_int_loader(n=256), depth=1)
        iter(pl)
        time.sleep(0.2)
        t0 = time.monotonic()
        pl.close()
        assert time.monotonic() - t0 < 3.0
        _assert_no_threads()


# ------------------------------------------------------------ device stage

class TestDeviceStage:
    def test_place_fn_output_yielded_directly_in_order(self):
        # the yielded batch IS place_fn's result — no wrapper type, so
        # user code inspecting batches keeps working (review regression)
        it = PrefetchIterator(iter([1, 2, 3]), depth=2,
                              place_fn=lambda b: b * 10)
        assert list(it) == [10, 20, 30]

    def test_engine_prefetched_loader_yields_inspectable_batches(self):
        """Iterating a prefetch-enabled deepspeed_io loader must yield
        the same pytree structure as the plain loader — device-placed
        leaves, not an opaque wrapper — so non-engine consumers
        (logging, custom metrics) keep working."""
        import jax
        engine = _make_engine(enabled=True)
        loader = engine.deepspeed_io(random_dataset(32, HIDDEN))
        assert loader.place_fn is not None       # device stage armed
        batches = list(iter(loader))
        plain = list(iter(DeepSpeedDataLoader(
            random_dataset(32, HIDDEN), batch_size=8, shuffle=True)))
        assert len(batches) == len(plain)
        for got, want in zip(batches, plain):
            x, y = got                           # tuple structure intact
            assert np.allclose(np.asarray(x), want[0])
            assert np.allclose(np.asarray(y), want[1])
            assert isinstance(x, jax.Array)      # pre-placed, global
            # re-placement through the engine is a no-transfer no-op:
            # the SAME buffers come back
            gb = engine._globalize_batch(got)
            assert gb[0] is x and gb[1] is y
        engine.close()


# ----------------------------------------------------------------- config

class TestConfig:
    def test_defaults(self):
        from deepspeed_tpu.runtime.config import DeepSpeedDataPrefetchConfig
        c = DeepSpeedDataPrefetchConfig({})
        assert c.enabled is False and c.depth == 2 and c.to_device is True

    def test_env_override(self, monkeypatch):
        from deepspeed_tpu.runtime.config import DeepSpeedDataPrefetchConfig
        monkeypatch.setenv("DS_DATA_PREFETCH", "1")
        assert DeepSpeedDataPrefetchConfig({}).enabled is True
        monkeypatch.setenv("DS_DATA_PREFETCH", "0")
        c = DeepSpeedDataPrefetchConfig(
            {"data_prefetch": {"enabled": True}})
        assert c.enabled is False

    def test_depth_validated(self):
        from deepspeed_tpu.runtime.config import (DeepSpeedConfigError,
                                                  DeepSpeedDataPrefetchConfig)
        with pytest.raises(DeepSpeedConfigError, match="depth"):
            DeepSpeedDataPrefetchConfig({"data_prefetch": {"depth": 0}})


# ------------------------------------------------------------- engine glue

def _make_engine(enabled=True, to_device=True, depth=2, telemetry=None,
                 steps_per_print=10 ** 9):
    cfg = {
        "train_batch_size": 8,
        "steps_per_print": steps_per_print,
        "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
        "data_prefetch": {"enabled": enabled, "depth": depth,
                          "to_device": to_device},
    }
    if telemetry:
        cfg["telemetry"] = telemetry
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=SimpleModel(hidden_dim=HIDDEN, nlayers=2), config=cfg,
        sample_batch=sample_batch(8, HIDDEN), seed=42)
    return engine


class TestEngineIntegration:
    def test_deepspeed_io_wraps_when_enabled(self):
        engine = _make_engine(enabled=True)
        loader = engine.deepspeed_io(random_dataset(32, HIDDEN))
        assert isinstance(loader, PrefetchLoader)
        assert loader.place_fn is not None      # single process: armed
        engine.close()

    def test_deepspeed_io_plain_when_disabled(self, monkeypatch):
        from deepspeed_tpu.runtime import engine as engine_mod
        warns = []
        monkeypatch.setattr(engine_mod.logger, "warning",
                            lambda msg, *a, **k: warns.append(str(msg)))
        engine = _make_engine(enabled=False)
        loader = engine.deepspeed_io(random_dataset(32, HIDDEN),
                                     num_local_io_workers=4)
        assert isinstance(loader, DeepSpeedDataLoader)
        assert loader.num_local_io_workers == 4
        # warn ONCE, not per loader
        engine.deepspeed_io(random_dataset(32, HIDDEN),
                            num_local_io_workers=4)
        assert sum("num_local_io_workers" in w for w in warns) == 1
        engine.close()

    def test_multiprocess_device_stage_armed_collective_free(
            self, monkeypatch):
        """The PR-10 lift: the device stage now RUNS on multi-process
        meshes — background placement uses verify=False, which performs
        no collectives by construction (the checksum/row-agreement
        collectives are deferred to the main thread at consumption), so
        the PR-5 deadlock cannot occur."""
        import jax
        engine = _make_engine(enabled=True)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        place = engine._prefetch_place_fn()
        assert place is not None                    # stage armed
        # the placement closure is the engine's _globalize_batch with the
        # background-thread contract: verification OFF
        assert place.func == engine._globalize_batch
        assert place.keywords.get("verify") is False
        eval_place = engine._prefetch_place_fn(for_train=False)
        assert eval_place.keywords == {"for_train": False,
                                       "verify": False}
        loader = engine.deepspeed_io(random_dataset(32, HIDDEN))
        assert isinstance(loader, PrefetchLoader)
        assert loader.place_fn is not None          # device stage on
        engine.close()

    def test_verify_false_placement_never_issues_collectives(
            self, monkeypatch):
        """verify=False placement (the background-thread path) must not
        call the checksum allgather even for broadcast leaves, and must
        not consume the first-occurrence key — the deferred main-thread
        check still runs for that leaf."""
        import jax
        engine = _make_engine(enabled=True)
        calls = []
        monkeypatch.setattr(
            engine, "_assert_identical_across_processes",
            lambda x: calls.append(np.shape(x)))
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        monkeypatch.setattr(
            jax, "make_array_from_process_local_data",
            lambda sh, x: np.asarray(x))
        # 2 "processes" x 8 dp -> 4 local rows; one [1, H] broadcast leaf
        batch = {"x": np.zeros((4, HIDDEN), np.float32),
                 "mask": np.ones((1, HIDDEN), np.float32)}
        engine._globalize_batch(batch, verify=False)
        assert calls == []                          # no collective issued
        assert not engine._broadcast_leaves_checked  # key not consumed
        engine._globalize_batch(batch, verify=True)
        assert len(calls) == 1                      # main-thread path does
        engine.close()

    def test_preplaced_global_batch_honours_verify_false(self, monkeypatch):
        """A user loader can yield ALREADY-global arrays straight into
        the background device stage: the pre-placed hand-back must still
        honour verify=False (no verification collectives off the main
        thread) — the deferred check runs when the consumption-side
        re-globalize lands in the same branch with verify=True."""
        import jax
        engine = _make_engine(enabled=True)
        monkeypatch.setattr(jax, "process_count", lambda: 2)
        calls = []
        monkeypatch.setattr(
            engine, "_verify_prefetched_batch",
            lambda b, for_train=True: calls.append(for_train))

        class _FakeGlobal(jax.Array):         # a non-addressable jax.Array
            is_fully_addressable = False
            shape = (8, HIDDEN)
            ndim = 2
            dtype = np.dtype(np.float32)
        batch = {"x": _FakeGlobal(), "y": _FakeGlobal()}
        out = engine._globalize_batch(batch, verify=False)  # background
        assert out is batch and calls == []
        out = engine._globalize_batch(batch, verify=True)   # consumption
        assert out is batch and calls == [True]
        engine.close()

    def test_deferred_verify_runs_on_main_thread(self, monkeypatch):
        """_verify_prefetched_batch (the consumption-side half) checksums
        replicated leaves exactly once, keyed by the shared
        first-occurrence set."""
        engine = _make_engine(enabled=True)

        class _FakeSharding:
            is_fully_replicated = True

        class _FakeLeaf:
            sharding = _FakeSharding()
            shape = (1, HIDDEN)
            dtype = np.float32

            def addressable_data(self, i):
                return np.ones(self.shape, np.float32)

        calls = []
        monkeypatch.setattr(
            engine, "_assert_identical_across_processes",
            lambda x: calls.append(np.shape(x)))
        batch = {"mask": _FakeLeaf()}
        engine._verify_prefetched_batch(batch)
        engine._verify_prefetched_batch(batch)      # second call: cached
        assert calls == [(1, HIDDEN)]
        engine.close()

    def test_deferred_eval_verify_one_collective_per_batch(
            self, monkeypatch):
        """The eval-route deferred row check issues ONE vector allgather
        for the whole batch (not one per leaf — that taxed every
        steady-state eval batch L serial round-trips) and still raises
        on cross-process row divergence."""
        from jax.experimental import multihost_utils
        engine = _make_engine(enabled=True)

        class _Leaf:
            sharding = None
            dtype = np.dtype(np.float32)

            def __init__(self, rows):
                self.shape = (rows, HIDDEN)

        calls = []

        def fake_allgather(x, divergent=False):
            calls.append(np.asarray(x))
            stacked = np.stack([np.asarray(x), np.asarray(x)])  # 2 procs
            if divergent:
                stacked[1, 0] += 1
            return stacked

        monkeypatch.setattr(multihost_utils, "process_allgather",
                            fake_allgather)
        batch = {"x": _Leaf(4), "y": _Leaf(4), "z": _Leaf(2)}
        engine._verify_prefetched_batch(batch, for_train=False)
        assert len(calls) == 1                      # one collective
        assert sorted(calls[0].tolist()) == [2, 4, 4]
        monkeypatch.setattr(
            multihost_utils, "process_allgather",
            lambda x: fake_allgather(x, divergent=True))
        with pytest.raises(ValueError, match="disagree across processes"):
            engine._verify_prefetched_batch(batch, for_train=False)
        engine.close()

    def test_eval_route_places_with_eval_semantics(self, monkeypatch):
        """An eval-route loader's device stage must place with
        for_train=False — train placement rejects/shards dim0==1 leaves
        differently than eval_batch's own path (review regression)."""
        engine = _make_engine(enabled=True)
        seen = []
        real = engine._globalize_batch
        monkeypatch.setattr(
            engine, "_globalize_batch",
            lambda b, for_train=True, verify=True:
            seen.append(for_train) or real(
                b, for_train=for_train, verify=verify))
        train_pl = engine.deepspeed_io(random_dataset(32, HIDDEN))
        train_pl.place_fn((np.zeros((8, HIDDEN), np.float32),
                           np.zeros((8, HIDDEN), np.float32)))
        eval_pl = engine.deepspeed_io(random_dataset(32, HIDDEN),
                                      route="eval")
        eval_pl.place_fn((np.zeros((8, HIDDEN), np.float32),
                          np.zeros((8, HIDDEN), np.float32)))
        assert seen == [True, False]
        engine.close()

    def test_to_device_false_disables_device_stage(self):
        engine = _make_engine(enabled=True, to_device=False)
        assert engine._prefetch_place_fn() is None
        engine.close()

    def test_losses_identical_with_and_without_prefetch(self):
        import jax

        def run(enabled):
            engine = _make_engine(enabled=enabled)
            it = RepeatingLoader(engine.deepspeed_io(
                random_dataset(64, HIDDEN)))
            losses = [float(jax.device_get(engine.train_batch(data_iter=it)))
                      for _ in range(6)]
            engine.close()
            return losses

        assert run(True) == run(False)

    def test_train_batch_wraps_user_iterator_once(self):
        engine = _make_engine(enabled=True)

        def forever():
            while True:
                for b in DeepSpeedDataLoader(random_dataset(64, HIDDEN),
                                             batch_size=8):
                    yield b

        it = forever()
        engine.train_batch(data_iter=it)
        assert len(engine._prefetch_wrap_cache) == 1
        (src, wrapped), = engine._prefetch_wrap_cache.values()
        assert src is it
        engine.train_batch(data_iter=it)
        assert len(engine._prefetch_wrap_cache) == 1
        (_, wrapped2), = engine._prefetch_wrap_cache.values()
        assert wrapped2 is wrapped      # one pipeline per iterator
        engine.close()
        _assert_no_threads()

    def test_stateful_iterator_not_wrapped(self):
        """A RepeatingLoader over a NON-prefetch-backed loader passes
        through unwrapped: a background puller outside the counter would
        advance its (epoch, batch_in_epoch) resume state ahead of what
        training consumed, so save_checkpoint(data_iter=...) would
        record a future position and a resumed run would skip batches.
        The supported composition — RepeatingLoader over a
        prefetch-enabled deepspeed_io loader — keeps both."""
        engine = _make_engine(enabled=True)
        it = RepeatingLoader(DeepSpeedDataLoader(
            random_dataset(64, HIDDEN), batch_size=8))
        engine.train_batch(data_iter=it)
        engine.train_batch(data_iter=it)
        assert not engine._prefetch_wrap_cache     # never wrapped
        # the recorded position is exactly what training consumed
        assert it.state_dict() == {"epoch": 0, "batch_in_epoch": 2}
        engine.close()
        _assert_no_threads()

    def test_no_double_pipeline_over_prefetch_backed_loader(self):
        engine = _make_engine(enabled=True)
        rl = RepeatingLoader(engine.deepspeed_io(random_dataset(64, HIDDEN)))
        engine.train_batch(data_iter=rl)
        assert engine._prefetch_wrap_cache == {}    # passed through as-is
        engine.close()

    def test_engine_close_stops_workers(self):
        engine = _make_engine(enabled=True)
        rl = RepeatingLoader(engine.deepspeed_io(random_dataset(64, HIDDEN)))
        for _ in range(3):
            engine.train_batch(data_iter=rl)
        assert _prefetch_threads()
        engine.close()
        _assert_no_threads()


# -------------------------------------------------------- acceptance e2e

def _slow_collate(samples):
    """20 ms of host input work per batch (decode/augment stand-in)
    against a ~ms-scale step — the ISSUE's acceptance scenario."""
    from deepspeed_tpu.runtime.dataloader import _default_collate
    time.sleep(0.02)
    return _default_collate(samples)


class TestAcceptance:
    def test_prefetch_collapses_input_wait_and_step_time(self):
        """THE acceptance e2e: same slow loader, prefetch off vs on —
        wall-clock per step drops materially, the ledger's steady-state
        input_wait fraction collapses, and the input_stall rule stops
        firing. 8 host workers x 20 ms/collate = 2.5 ms/batch service
        against a ~10 ms step, so the overlap is total — the consumer
        never waits."""
        import tempfile

        hidden = 256                    # ~9.5 ms step: clearly above the
        # 2.5 ms service rate (or steady-state windows would sit at the
        # rule threshold) yet small against the 20 ms serial stall

        def run(enabled):
            tmp = tempfile.mkdtemp(prefix="prefetch_e2e_")
            engine, _, _, _ = deepspeed_tpu.initialize(
                model=SimpleModel(hidden_dim=hidden, nlayers=2),
                config={
                    "train_batch_size": 8,
                    "steps_per_print": 4,
                    "optimizer": {"type": "Adam", "params": {"lr": 1e-2}},
                    "data_prefetch": {"enabled": enabled, "depth": 8},
                    "telemetry": {
                        "enabled": True, "trace": False, "jsonl": False,
                        "prometheus": False,
                        # warmup 2: the rules must not judge the
                        # pipeline's own cold ramp-up (first fill of
                        # the depth buffer), only steady state
                        "goodput": {"enabled": True, "cadence": 2,
                                    "warmup_windows": 2,
                                    "profiler_capture": False,
                                    "snapshot_file":
                                        tmp + "/GOODPUT.json"}}},
                sample_batch=sample_batch(8, hidden), seed=42)
            # 256 rows = 32 batches/epoch: the measured window stays
            # inside one epoch (each wrap-around rebuilds the pipeline —
            # a cold start the steady-state claim shouldn't include)
            it = RepeatingLoader(engine.deepspeed_io(
                random_dataset(256, hidden), num_local_io_workers=8,
                collate_fn=_slow_collate))
            engine.train_batch(data_iter=it)        # compile step
            steps = 10
            t0 = time.perf_counter()
            for _ in range(steps):
                engine.train_batch(data_iter=it)
            per_step = (time.perf_counter() - t0) / steps
            rep = engine.goodput_report()
            engine.close()
            # steady-state input_wait fraction: the cadence windows past
            # warmup (what the input_stall rule judges) — whole-run totals
            # would dilute it with engine init + the first-step compile
            steady = [w for w in rep["windows"]
                      if not w.get("forced") and w["index"] >= 2]
            frac = (sum(w["categories_s"]["input_wait"] for w in steady)
                    / max(sum(w["dur_s"] for w in steady), 1e-9))
            stalls = rep["counters"]["anomaly_counts"].get("input_stall", 0)
            return per_step, frac, stalls

        serial_step, serial_frac, serial_stalls = run(False)
        prefetch_step, prefetch_frac, prefetch_stalls = run(True)
        # serial pays the full 20 ms of input work on the critical path
        assert serial_step >= 0.02
        assert serial_stalls >= 1            # PR-4 rule sees the stall
        # overlapped: materially faster and the rule goes quiet
        assert prefetch_step <= serial_step * 0.7, (
            f"prefetch {prefetch_step * 1e3:.1f} ms/step vs serial "
            f"{serial_step * 1e3:.1f} — no overlap happened")
        assert prefetch_frac <= serial_frac * 0.5, (
            f"input_wait fraction {prefetch_frac:.2f} did not collapse "
            f"(serial {serial_frac:.2f})")
        assert prefetch_stalls == 0

    def test_prefetch_hits_dominate_on_fast_input(self):
        """When the input pipeline keeps up, steady state is all hits
        (an input-BOUND pipeline legitimately misses — the consumer
        outruns it — so this uses a fast dataset)."""
        engine = _make_engine(
            enabled=True,
            telemetry={"enabled": True, "trace": False, "jsonl": False,
                       "prometheus": False})
        it = RepeatingLoader(engine.deepspeed_io(random_dataset(64, HIDDEN)))
        for _ in range(8):
            engine.train_batch(data_iter=it)
        snap = engine.telemetry.registry.snapshot()
        hits = snap["prefetch_hits_total"][0]["value"]
        misses = snap["prefetch_misses_total"][0]["value"]
        assert hits + misses == 8
        assert hits >= 5
        engine.close()
