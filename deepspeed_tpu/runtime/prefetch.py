"""Asynchronous input pipeline: background prefetch + device double-buffering.

PR 4's goodput ledger made input stalls *visible* (``input_wait``); this
module makes them *removable*. Today the engine's step loop runs
``next(data_iter)`` -> collate -> ``_globalize_batch`` ``device_put`` ->
dispatch fully serialized on the critical path, so every millisecond of
host-side batch work and H2D transfer is dead device time. The
:class:`PrefetchLoader` wraps any engine data source with a bounded
two-stage background pipeline (the tf.data / Flax ``prefetch_to_device``
idiom, and the reference DeepSpeed's implicit contract via its
worker-backed dataloaders):

* **host stage** — worker thread(s) pull + collate the next ``depth``
  batches. A :class:`~deepspeed_tpu.runtime.dataloader.DeepSpeedDataLoader`
  exposes its index plan / materialize split, so ``num_local_io_workers``
  workers collate *concurrently* while a filler thread preserves batch
  order; a generic iterator gets one puller thread (generators are not
  concurrently re-entrant).
* **device stage** — a placement thread runs the engine's
  ``_globalize_batch`` (``device_put``) for batch N+1 while step N
  computes, so the H2D copy overlaps device execution. The yielded batch
  is the SAME pytree with device-placed global leaves — not a wrapper —
  so user code that inspects batches keeps working, and the engine's own
  ``device_put`` against the identical sharding is a no-transfer no-op
  (same buffer, re-checked under jax 0.9). The stage runs on multi-process
  meshes too: the engine passes ``verify=False`` placement, which is
  collective-free by construction — the broadcast-leaf checksum
  allgather and eval row-count agreement are deferred to the MAIN thread
  at consumption (``engine._verify_prefetched_batch``), so a
  background-thread collective can never race a main-thread one (the
  deadlock that made PR 5 restrict the stage to single-process runs).

Hard edges handled here, all unit-pinned (``tests/unit/test_prefetch.py``):

* a worker exception is re-raised at the consumer's ``next()``, in
  sequence position (batches before it are delivered first);
* ``StopIteration`` / epoch semantics are identical to the unwrapped
  loader — each ``iter()`` drains exactly one epoch, so a wrapping
  ``RepeatingLoader`` still fires ``set_epoch`` in order on wrap-around
  before the next epoch's first pull;
* at most ``depth`` batches are materialized inside the pipeline (a
  semaphore gates the filler; the consumer returns permits);
* shutdown is leak-free: ``close()`` (idempotent), context manager and
  engine teardown stop + join the (daemon) threads with sentinel
  wake-ups; an iterator ABANDONED mid-epoch is reclaimed by GC — the
  threads hold only the shared :class:`_PipelineState`, never the
  iterator, so ``weakref.finalize`` fires, stops the pipeline, and also
  covers interpreter exit;
* background threads run under the goodput ledger's
  ``suppress_attribution`` so overlapped input work books ZERO
  ``input_wait`` — the consumer's near-zero ``next()`` wait is the real
  number, which is exactly what drives the PR-4 ``input_stall`` rule
  quiet on a prefetched run.

Telemetry: ``prefetch_hits_total`` / ``prefetch_misses_total`` counters
(was the next batch ready when the consumer asked?) and a
``prefetch_depth_occupancy`` gauge flow through whatever metrics registry
is installed (the engine's TelemetryManager installs its registry as the
process global, so JSONL/Prometheus sinks carry them for free).
"""

import queue
import threading
import weakref

from deepspeed_tpu.telemetry import metrics as _metrics
from deepspeed_tpu.telemetry.ledger import suppress_attribution
from deepspeed_tpu.utils.logging import logger

_END = "end"
_ERR = "err"
_OK = "ok"

# close()-join grace per thread; they are daemon threads, so a pathological
# hang in user collate/placement code degrades to a leaked daemon (and a
# warning), never a blocked interpreter exit
_JOIN_TIMEOUT_S = 5.0
_POLL_S = 0.2


class _Slot:
    """A minimal future: one materialized batch, or the exception its
    materialization raised. Custom instead of concurrent.futures because
    ThreadPoolExecutor threads are non-daemon and atexit-joined — a hung
    collate would block interpreter exit, the exact leak close() exists
    to prevent."""
    __slots__ = ("_ev", "_value", "_exc")

    def __init__(self):
        self._ev = threading.Event()
        self._value = None
        self._exc = None

    def set_result(self, value):
        self._value = value
        self._ev.set()

    def set_exception(self, exc):
        self._exc = exc
        self._ev.set()

    def done(self):
        return self._ev.is_set()

    def wait_ready(self, stop=None):
        """Block until the slot is filled; with *stop*, poll so a close()
        can interrupt the wait. Returns False iff stopped unfilled — a
        close() may leave queued slots no worker will ever fill, and an
        untimed Event.wait() there blocks its thread forever."""
        if stop is None:
            self._ev.wait()
            return True
        while not self._ev.wait(timeout=_POLL_S):
            if stop.is_set():
                return False
        return True

    def result(self):
        self._ev.wait()
        if self._exc is not None:
            raise self._exc
        return self._value


class _PipelineState:
    """Everything the pipeline threads share. Threads (and the GC
    finalizer) hold THIS object, never the iterator — so abandoning an
    iterator mid-epoch lets GC collect it, which fires the finalizer,
    which stops these threads. Holding ``self`` in a thread target would
    pin the iterator alive forever (the parked filler never exits)."""
    __slots__ = ("stop", "sem", "hostq", "outq", "workq", "threads")

    def __init__(self, depth, device_stage):
        self.stop = threading.Event()
        self.sem = threading.Semaphore(depth)
        self.hostq = queue.Queue()
        self.outq = queue.Queue() if device_stage else self.hostq
        self.workq = None
        self.threads = []


def _wake_and_stop(state):
    """Stop flag + one wake sentinel per blocked wait site, so no thread
    sleeps out a poll timeout (an epoch wrap-around rebuilds the
    pipeline — join latency here is train-loop latency)."""
    state.stop.set()
    n = max(1, len(state.threads))
    if state.workq is not None:
        for _ in range(n):
            state.workq.put(None)
    state.hostq.put(None)
    if state.outq is not state.hostq:
        # device stage armed: the hostq sentinel stops the device
        # thread but never reaches a consumer blocked in outq.get()
        state.outq.put(None)
    state.sem.release(n)              # filler parked on the depth gate


def _acquire_permit(state):
    """Depth-semaphore acquire that aborts on stop."""
    while not state.stop.is_set():
        if state.sem.acquire(timeout=_POLL_S):
            return True
    return False


def _fill_indexed(state, loader):
    try:
        for idx in loader._index_plan():
            if not _acquire_permit(state):
                return
            slot = _Slot()
            state.workq.put((idx, slot))
            state.hostq.put((_OK, slot))
        state.hostq.put((_END, None))
    except BaseException as e:                 # plan-time failure
        state.hostq.put((_ERR, e))


def _worker_loop(state, loader):
    while not state.stop.is_set():
        try:
            item = state.workq.get(timeout=_POLL_S)
        except queue.Empty:
            continue
        if item is None:              # close() wake sentinel
            return
        idx, slot = item
        try:
            with suppress_attribution():
                slot.set_result(loader.materialize(idx))
        except BaseException as e:
            slot.set_exception(e)


def _fill_generic(state, src):
    while not state.stop.is_set():
        if not _acquire_permit(state):
            return
        try:
            with suppress_attribution():
                batch = next(src)
        except StopIteration:
            state.hostq.put((_END, None))
            return
        except BaseException as e:
            state.hostq.put((_ERR, e))
            return
        state.hostq.put((_OK, batch))


def _device_loop(state, place_fn):
    while not state.stop.is_set():
        try:
            item = state.hostq.get(timeout=_POLL_S)
        except queue.Empty:
            continue
        if item is None:              # close() wake sentinel
            return
        kind, payload = item
        if kind != _OK:
            state.outq.put((kind, payload))
            return
        if isinstance(payload, _Slot) and \
                not payload.wait_ready(state.stop):
            return                    # closed with the slot never filled
        try:
            batch = payload.result() if isinstance(payload, _Slot) \
                else payload
            with suppress_attribution():
                placed = place_fn(batch)
        except BaseException as e:
            state.outq.put((_ERR, e))
            return
        state.outq.put((_OK, placed))


class PrefetchIterator:
    """One epoch's pipeline. Built by :class:`PrefetchLoader`; usable
    directly to wrap an arbitrary iterator (the engine does this for a
    user-supplied ``data_iter``)."""

    def __init__(self, source, depth=2, num_workers=1, place_fn=None,
                 loader=None, name="prefetch"):
        self.depth = max(1, int(depth))
        self._name = name
        self._finished = False
        self._closed = False
        self._error = None
        # indexed mode: the loader's index plan is cheap pure numpy, so the
        # filler computes it and N workers materialize (dataset fetch +
        # collate) concurrently; order is preserved because slots enter the
        # host queue in plan order. Generic mode: one puller owns the
        # iterator (generators cannot be entered from two threads).
        indexed = (loader is not None
                   and hasattr(loader, "_index_plan")
                   and hasattr(loader, "materialize"))
        workers = max(1, int(num_workers or 1))
        if not indexed and workers > 1:
            _warn_once(
                "generic_iter_workers",
                f"data_prefetch: source {type(source).__name__!r} is not an "
                f"indexable DeepSpeedDataLoader; the host stage runs ONE "
                f"puller thread (iterators are not concurrently "
                f"re-entrant), ignoring num_local_io_workers={workers}")
            workers = 1
        workers = min(workers, self.depth)
        reg = _metrics.get_registry()
        self._hits = reg.counter(
            "prefetch_hits_total",
            "next() calls served by an already-materialized batch")
        self._misses = reg.counter(
            "prefetch_misses_total",
            "next() calls that had to wait on the input pipeline")
        self._occupancy = reg.gauge(
            "prefetch_depth_occupancy",
            "batches ready in the prefetch output queue at next()")

        state = self._state = _PipelineState(
            self.depth, device_stage=place_fn is not None)
        if indexed:
            state.workq = queue.Queue()
            for i in range(workers):
                self._spawn(_worker_loop, (state, loader), f"w{i}")
            self._spawn(_fill_indexed, (state, loader), "fill")
        else:
            self._spawn(_fill_generic, (state, iter(source)), "fill")
        if place_fn is not None:
            self._spawn(_device_loop, (state, place_fn), "place")
        # abandoned-iterator backstop: fires at GC (threads hold only
        # `state`, so dropping the iterator really does free it) and at
        # interpreter exit; stops the pipeline without joining (the
        # daemon threads drain themselves within a poll interval)
        self._finalizer = weakref.finalize(self, _wake_and_stop, state)

    def _spawn(self, fn, args, tag):
        t = threading.Thread(target=fn, args=args,
                             name=f"ds-{self._name}-{tag}", daemon=True)
        self._state.threads.append(t)
        t.start()

    # ------------------------------------------------------------ consumer
    def __iter__(self):
        return self

    def __next__(self):
        if self._error is not None:
            # a failed pipeline stays failed: repeating the exception is
            # honest; StopIteration here would silently truncate the epoch
            raise self._error
        if self._finished:
            raise StopIteration
        outq = self._state.outq
        try:
            item = outq.get_nowait()
            ready = True
        except queue.Empty:
            ready = False
            item = outq.get()
        if item is None:              # closed under a blocked consumer
            raise StopIteration
        kind, payload = item
        if kind == _END:
            self._finish()
            raise StopIteration
        if kind == _ERR:
            self._error = payload
            self._finish()
            raise payload
        if isinstance(payload, _Slot):
            # host future: a "hit" means the materialization had finished
            # by the time the consumer asked
            ready = ready and payload.done()
            if not payload.wait_ready(self._state.stop):
                raise StopIteration   # closed with the slot never filled
            try:
                payload = payload.result()
            except BaseException as e:
                self._error = e
                self._finish()
                raise
        (self._hits if ready else self._misses).inc()
        self._occupancy.set(outq.qsize())
        self._state.sem.release()
        return payload

    # ------------------------------------------------------------ shutdown
    def _finish(self):
        """Natural end (or error): stop + join the pipeline threads."""
        self._finished = True
        self.close()

    def close(self):
        if self._closed:
            return
        self._closed = True
        self._finished = True
        _wake_and_stop(self._state)
        for t in self._state.threads:
            t.join(timeout=_JOIN_TIMEOUT_S)
            if t.is_alive():
                logger.warning(
                    f"data_prefetch: thread {t.name} did not stop within "
                    f"{_JOIN_TIMEOUT_S}s (daemon; it cannot block exit)")
        self._finalizer.detach()      # already shut down; nothing for GC

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


class PrefetchLoader:
    """Loader-shaped wrapper: each ``iter()`` spawns one
    :class:`PrefetchIterator` epoch pipeline over ``iter(loader)``.

    Delegates ``__len__`` / ``set_epoch`` / ``.epoch`` to the wrapped
    loader so a surrounding ``RepeatingLoader`` (or a resume path) sees
    the ordinary loader surface. ``close()`` stops every live iterator's
    pipeline; the loader is also a context manager."""

    def __init__(self, loader, depth=2, num_workers=1, place_fn=None,
                 name="prefetch"):
        self.loader = loader
        self.depth = depth
        self.num_workers = num_workers
        self.place_fn = place_fn
        self._name = name
        self._iters = []                      # weakrefs to live pipelines

    def __len__(self):
        return len(self.loader)

    def set_epoch(self, epoch):
        set_epoch = getattr(self.loader, "set_epoch", None)
        if set_epoch is not None:
            set_epoch(epoch)

    def set_resume(self, batch_in_epoch):
        """Mid-epoch resume passthrough (RepeatingLoader.load_state_dict):
        the skip lives in the wrapped loader's index plan, so the next
        ``iter()``'s pipeline simply never schedules the skipped
        batches."""
        set_resume = getattr(self.loader, "set_resume", None)
        if set_resume is not None:
            set_resume(batch_in_epoch)
        else:
            raise AttributeError(
                f"wrapped loader {type(self.loader).__name__!r} has no "
                f"set_resume; mid-epoch resume needs a "
                f"DeepSpeedDataLoader-style index plan")

    @property
    def epoch(self):
        return getattr(self.loader, "epoch", 0)

    def __iter__(self):
        # DeepSpeedDataLoader: hand the loader itself over so the host
        # stage can use its index-plan/materialize split (N workers);
        # anything else is pulled through its ordinary iterator protocol
        indexed = (hasattr(self.loader, "_index_plan")
                   and hasattr(self.loader, "materialize"))
        it = PrefetchIterator(
            self.loader, depth=self.depth, num_workers=self.num_workers,
            place_fn=self.place_fn,
            loader=self.loader if indexed else None, name=self._name)
        self._iters = [r for r in self._iters if r() is not None]
        self._iters.append(weakref.ref(it))
        return it

    def close(self):
        for ref in self._iters:
            it = ref()
            if it is not None:
                it.close()
        self._iters = []

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


_WARNED = set()


def _warn_once(key, msg):
    if key not in _WARNED:
        _WARNED.add(key)
        logger.warning(msg)
