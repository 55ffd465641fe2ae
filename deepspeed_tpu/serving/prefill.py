"""Chunked prefill — fill a prompt's KV in fixed-size slices.

A synchronous full-prompt prefill stalls every running request for the
whole prompt forward (hundreds of tokens of compute between two decode
steps). Chunking bounds that stall: each scheduler iteration advances the
prefilling request by at most ``chunk_size`` tokens, interleaved with the
decode batch (Sarathi-style chunked prefill; the scheduler picks at most
one chunk per slot and iteration).

One compiled program serves every chunk: chunks are always ``chunk_size``
wide, the final partial chunk is padded, and the pad positions write to
the null block (``n_valid`` masks them). A dispatch carries ``rows``
chunks of distinct slots, one a row (:func:`prefill_rows`); a step's
chunks go out in the plan's order, ``rows`` at a time, and only the last
dispatch of a step has pad rows. The planner covers ``prompt[:-1]`` only
— the last prompt token is the request's first decode input, so its KV is
written by the decode step that samples the first generated token (TTFT
therefore includes exactly one decode step after the last chunk).

Prefix-cache composition: admission may pre-set ``cached_len`` past 0
when whole prompt blocks were matched read-only from the prefix index
(scheduler ``_admit``). ``remaining`` then naturally plans chunks from
the first uncached token — a fully-cached prefix needs ZERO chunk
dispatches here, just the block-table copy the scheduler already did.
"""

import dataclasses

import numpy as np

# Token positions at which a prefill dispatch reads its weights at the
# pace of its products. A dispatch reads every weight once whatever rows
# it holds, and a bfloat16 weight does 2 operations for its 2 bytes for
# each token that uses it; the v5e's ridge is 197 TFLOP/s over 819 GB/s,
# about 240 operations a byte (benchmark/peaks.json). Fewer positions
# read the weights again for every chunk; more add pad rows, each about a
# whole chunk's time (on a v5e at gpt2-medium's widths a chunk of 128
# costs 0.30 ms a call and 1.64 a row: PERF.md, Findings).
RIDGE_POSITIONS = 256


def prefill_rows(chunk_size: int, max_batch: int) -> int:
    """Chunks a prefill dispatch carries: enough to hold
    ``RIDGE_POSITIONS`` positions, at least one, at most one a slot."""
    return min(int(max_batch), max(1, -(-RIDGE_POSITIONS // int(chunk_size))))


@dataclasses.dataclass
class Chunk:
    """One planned chunk of ``req``: ``tokens [C]`` null-padded, its first
    position, its real tokens, and how many of them lie below the
    request's eviction high-water mark (re-prefilled)."""
    req: object
    tokens: np.ndarray
    start: int
    n_valid: int
    n_recompute: int


class ChunkedPrefill:
    def __init__(self, prefill_fn, chunk_size: int, max_batch: int = 1):
        """``prefill_fn``: the runner's ``prefill_chunk`` (the server
        passes its compile-watch-wrapped form so chunk signatures are
        tracked), called as ``(params, scales, pools, bt, tokens, start,
        n_valid, slot)`` and returning the pools."""
        assert chunk_size >= 1
        self.prefill_fn = prefill_fn
        self.chunk_size = int(chunk_size)
        self.rows = prefill_rows(chunk_size, max_batch)

    def remaining(self, req) -> int:
        """Prompt tokens still to cache (prefill target is P-1)."""
        return max(0, len(req.full_prompt) - 1 - req.cached_len)

    def plan(self, req) -> Chunk:
        """The next chunk of ``req``. ``n_recompute`` counts its tokens
        below the request's eviction high-water mark — positions whose KV
        existed before a preemption threw it away, i.e. compute this chunk
        pays a SECOND time (the slot-step ledger and
        ``serving_recompute_tokens_total`` book preemption cost from
        it)."""
        start = req.cached_len
        n_valid = min(self.chunk_size, self.remaining(req))
        assert n_valid > 0, "plan on a fully prefilled request"
        tokens = np.zeros((self.chunk_size,), np.int32)
        tokens[:n_valid] = req.full_prompt[start:start + n_valid]
        n_recompute = max(0, min(start + n_valid,
                                 getattr(req, "max_cached_len", 0)) - start)
        return Chunk(req, tokens, start, n_valid, n_recompute)

    def dispatch(self, params, scales, pools, chunks, max_blocks: int):
        """ONE call of the prefill program for ``chunks`` (at most
        ``rows``, each of its own slot), pad rows after them (``n_valid``
        0, a table of zeros); a single row goes without the row dimension.
        Advances each request's ``cached_len``; returns the pools."""
        R, C = self.rows, self.chunk_size
        assert 0 < len(chunks) <= R, (len(chunks), R)
        assert len({c.req.slot for c in chunks}) == len(chunks), \
            "two chunks of one slot in a dispatch"
        bt = np.zeros((R, max_blocks), np.int32)
        tokens = np.zeros((R, C), np.int32)
        start, n_valid, slot = (np.zeros((R,), np.int32) for _ in range(3))
        for i, c in enumerate(chunks):
            bt[i, :len(c.req.block_table)] = c.req.block_table
            tokens[i], start[i], n_valid[i] = c.tokens, c.start, c.n_valid
            slot[i] = c.req.slot
        args = (bt, tokens, start, n_valid, slot)
        if R == 1:
            args = tuple(a[0] for a in args)
        pools = self.prefill_fn(params, scales, pools, *args)
        for c in chunks:
            c.req.cached_len += c.n_valid
        return pools
