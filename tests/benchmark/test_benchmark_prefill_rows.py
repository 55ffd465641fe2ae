"""The two readers of the prefill program's rows on hand-made records:
``offline_prefill_chunks_per_dispatch`` (chunks a ``serving_prefill_dispatch``
span carries) and ``offline_prefill_device_ms_per_chunk`` (device time of
the prefill programs over the ``serving_prefill`` spans), each on a program
that shares calls, on one that dispatches every chunk alone (no dispatch
span: the first reads nothing, the second the program's mean), and on an
empty record. The times here are written, not taken."""

import types

import pytest

from benchmark import harness, program_spans

W0, W1 = 100.0, 110.0           # the window, seconds on perf_counter


def ev(name, start_ms, dur_ms, **args):
    """A complete Chrome-trace event ``start_ms`` after the window opens."""
    return {"name": name, "ph": "X", "ts": int(W0 * 1e6 + start_ms * 1e3),
            "dur": int(dur_ms * 1e3), "pid": 1, "tid": 1, "args": args}


def chunk(start_ms, req):
    return ev("serving_prefill", start_ms, 0.1, req=req, start=0, tokens=8,
              recompute=0)


# two steps: three chunks in calls of two rows (2 + 1), then one alone
SHARED = [ev("serving_step", 0, 20), ev("serving_prefill_dispatch", 1, 4,
                                        rows=2, chunks=2),
          chunk(1.1, 0), chunk(1.3, 1),
          ev("serving_prefill_dispatch", 6, 3, rows=2, chunks=1),
          chunk(6.1, 2), ev("serving_decode", 10, 5, batch=3),
          ev("serving_step", 30, 20),
          ev("serving_prefill_dispatch", 31, 3, rows=2, chunks=1),
          chunk(31.1, 3)]
# the same chunks, each its own program and span, as before rows
ALONE = [ev("serving_step", 0, 20), chunk(1, 0), chunk(3, 1), chunk(6, 2),
         ev("serving_step", 30, 20), chunk(31, 3)]
PROGRAMS = {"jit__prefill_impl": [3.0e-3, 2.5e-3, 2.5e-3],
            "jit__decode_impl": [19.6e-3] * 2}


def ctx(monkeypatch, events, programs=PROGRAMS):
    monkeypatch.setattr(program_spans, "program_events", lambda: events)
    return {"spans": [("window", W0, W1)],
            "trace": types.SimpleNamespace(programs=programs)}


def test_chunks_per_dispatch_is_the_dispatch_spans_chunks_over_their_count(
        monkeypatch):
    read = harness.load_reader("offline_prefill_chunks_per_dispatch")
    assert read(ctx(monkeypatch, SHARED)) == pytest.approx(4 / 3)


@pytest.mark.parametrize("events", [ALONE, []], ids=["alone", "empty"])
def test_chunks_per_dispatch_reads_nothing_without_dispatch_spans(
        monkeypatch, events):
    read = harness.load_reader("offline_prefill_chunks_per_dispatch")
    assert read(ctx(monkeypatch, events)) is None


@pytest.mark.parametrize("events", [SHARED, ALONE], ids=["shared", "alone"])
def test_device_ms_per_chunk_is_the_prefill_programs_over_the_chunks(
        monkeypatch, events):
    """8 ms of prefill programs over four chunks, however many calls
    carried them; the decode program is not counted."""
    read = harness.load_reader("offline_prefill_device_ms_per_chunk")
    assert read(ctx(monkeypatch, events)) == pytest.approx(2.0)


@pytest.mark.parametrize("events, programs", [
    (SHARED, {"jit__decode_impl": [0.02]}), ([], PROGRAMS)],
    ids=["no-program", "no-span"])
def test_device_ms_per_chunk_reads_nothing_without_both(monkeypatch, events,
                                                        programs):
    read = harness.load_reader("offline_prefill_device_ms_per_chunk")
    assert read(ctx(monkeypatch, events, programs)) is None
