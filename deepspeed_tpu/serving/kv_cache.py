"""Paged KV cache — fixed-size blocks + a block allocator + block tables.

The production-serving memory model (vLLM/PagedAttention, SOSP '23): the
decode KV cache is ONE pool of fixed-size blocks shared by every request,
and each request owns an ordered *block table* mapping its logical token
positions onto pool blocks. Heterogeneous prompt/generation lengths then
share a single static-shaped compiled decode step — the per-step program
always sees the same ``[n_layer*num_blocks, block_size, W]`` pools plus
small int32 tables, and only the *values* change as requests come and
go, so XLA compiles the decode step exactly once for the whole serving
lifetime.

Split of responsibilities:

* ``BlockAllocator`` — host-side free-list over block ids, REFCOUNTED:
  a block may be mapped read-only into several requests' tables at once
  (shared-prefix reuse) and returns to the free list only when its last
  holder releases it. Block 0 is reserved as the *null* block: inactive
  batch slots (and the padded tail of a prefill chunk) route their
  writes there, which keeps the compiled step branch-free, and it is
  never refcounted or handed out. ``free``/``allocate``/``share`` are
  guarded against leaks, double-frees and foreign frees — the guard
  names the holding request and the refcount at failure so an
  accounting bug fails loudly instead of silently corrupting another
  request's cache.
* ``PrefixCache`` — content-addressed index over FULL blocks: each full
  block is keyed by a chain digest of ``(parent_digest, token_ids,
  position_base)`` salted with the KV dtype, in a bounded LRU.
  Admission walks a prompt against it and maps every hit
  read-only (prefill then starts at the first uncached token); the
  index holds one reference per resident block, so a block whose last
  *request* finished stays reusable until LRU eviction or
  ``reclaim()`` — which the scheduler calls before any preemption
  fires.
* ``PagedKVCache`` — owns the device pools (K and V as token rows, and
  for the int8 KV layout the per-row fp32 scales; for latent attention
  ONE pool ``kv`` whose row is the token's latent; for layers that carry
  a state, pools of one row a slot beside them) plus the one write
  the runner traces into a compiled step: ``write_layers`` (one scatter
  per pool for a step's tokens in the layers that ran). The reads are
  serving/paged_attention.py's: they walk the blocks that hold tokens
  where they lie and never materialise a slot's window.
"""

import hashlib
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.ops.transformer.decode import quantize_kv

# a TPU vreg's lane count: the pools' minor dimension is kept a whole
# number of them (PagedKVCache docstring)
_LANES = 128


class BlockAllocatorError(RuntimeError):
    pass


class BlockAllocator:
    """Refcounted free-list allocator over ``num_blocks`` pool blocks.

    Block 0 is reserved (the null/trash block) and never handed out.
    ``allocate`` is all-or-nothing; ``share`` adds a reference to an
    already-live block (shared-prefix mapping); ``free`` drops one
    reference and recycles the block at zero. Double-frees and foreign
    frees raise with the holding request and the refcount at failure
    named, so an accounting bug fails loudly instead of silently
    corrupting another request's cache.
    """

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 usable + the reserved null block), "
                f"got {num_blocks}")
        self.num_blocks = num_blocks
        # LIFO free list: recently-freed blocks are re-used first (their
        # pool pages are hot)
        self._free = list(range(num_blocks - 1, 0, -1))
        # block id -> live reference count (the historical name is kept:
        # the membership/len reads the tests pin still hold)
        self._allocated = {}
        # block id -> one owner label per reference (len == refcount);
        # labels are request ids / "prefix-cache" / None, purely for the
        # failure messages — policy never reads them
        self._owners = {}
        # block id -> label that dropped the LAST reference (what a
        # double-free names as the probable culprit)
        self._last_freed_by = {}

    @staticmethod
    def _label(owner):
        return "<anonymous>" if owner is None else f"request {owner!r}"

    @property
    def num_usable(self) -> int:
        return self.num_blocks - 1

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._allocated)

    def occupancy(self) -> float:
        """Fraction of usable blocks currently holding live references
        (request tables AND prefix-cache residency)."""
        return len(self._allocated) / max(1, self.num_usable)

    def can_allocate(self, n: int) -> bool:
        return n <= len(self._free)

    def refcount(self, block: int) -> int:
        return self._allocated.get(block, 0)

    def allocate(self, n: int, owner=None):
        """Return ``n`` block ids (each with refcount 1), or ``None``
        when the pool can't cover the request (all-or-nothing; no
        partial grants)."""
        if n < 0:
            raise ValueError(f"allocate({n})")
        if n > len(self._free):
            return None
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._allocated[b] = 1
            self._owners[b] = [owner]
        return blocks

    def share(self, blocks, owner=None):
        """Add one reference apiece to already-live blocks (a read-only
        shared-prefix mapping). The null block and free blocks are
        rejected — sharing dead storage is an indexing bug."""
        for b in blocks:
            if b == 0:
                raise BlockAllocatorError(
                    "share of the reserved null block 0 — the null block "
                    "is never refcounted")
            if b not in self._allocated:
                raise BlockAllocatorError(
                    f"share of block {b} which is not allocated "
                    f"(refcount 0) — stale prefix-index entry?")
            self._allocated[b] += 1
            self._owners[b].append(owner)

    def free(self, blocks, owner=None):
        """Drop one reference per block; a block returns to the free
        list when its last reference goes. With ``owner`` given, the
        reference released must actually be held by that owner."""
        for b in blocks:
            rc = self._allocated.get(b, 0)
            if rc == 0:
                culprit = self._last_freed_by.get(b)
                hint = (f"; last released by {self._label(culprit)}"
                        if b in self._last_freed_by else "")
                raise BlockAllocatorError(
                    f"free of block {b} which is not allocated "
                    f"(refcount 0{hint}) — double-free or foreign id")
            owners = self._owners[b]
            if owner is not None and owner not in owners:
                holders = ", ".join(self._label(o) for o in owners)
                raise BlockAllocatorError(
                    f"free of block {b} by {self._label(owner)} which "
                    f"holds no reference to it (refcount {rc}, held by "
                    f"{holders}) — foreign id")
            owners.remove(owner if owner in owners else owners[-1])
            if rc == 1:
                del self._allocated[b]
                del self._owners[b]
                self._last_freed_by[b] = owner
                self._free.append(b)
            else:
                self._allocated[b] = rc - 1

    def check_consistency(self):
        """Invariant check used by the tests: free ∪ allocated is exactly
        the usable id space, the two sets are disjoint, and every live
        block carries one owner label per reference."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise BlockAllocatorError("duplicate ids on the free list")
        live = set(self._allocated)
        if free & live:
            raise BlockAllocatorError(
                f"ids both free and allocated: {free & live}")
        universe = set(range(1, self.num_blocks))
        if free | live != universe:
            raise BlockAllocatorError(
                f"leaked ids: {universe - (free | live)}")
        if 0 in live:
            raise BlockAllocatorError("null block 0 acquired a refcount")
        for b, rc in self._allocated.items():
            if rc < 1 or len(self._owners.get(b, ())) != rc:
                raise BlockAllocatorError(
                    f"block {b}: refcount {rc} != {len(self._owners[b])} "
                    f"owner labels")
        return True


class PrefixCache:
    """Content-addressed shared-prefix index over FULL KV blocks.

    Every full block a request writes is registered under a *chain
    digest* — ``H(parent_digest, token_ids, position_base)`` with the
    cache salt (KV dtype, block size) folded into the root — so a hit
    certifies the ENTIRE prefix up to and including the
    block, not just its own tokens (position_base makes the digest
    absolute-position-aware; learned position embeddings mean the same
    tokens at a different offset are different KV). Admission walks a
    prompt block-by-block against the index and maps every hit
    read-only; the index holds ONE allocator reference per resident
    block, so finished requests' prefixes stay warm until LRU eviction
    (capacity bound) or :meth:`reclaim` — the scheduler's
    cheaper-than-preemption block source.

    int8-KV pools share bit-exactly: quantize-on-write makes a block's
    stored bytes a deterministic function of (tokens, positions,
    params), so a reader cannot tell a shared block from one it wrote
    itself.
    """

    OWNER = "prefix-cache"

    def __init__(self, allocator, block_size, capacity_blocks=0, salt=""):
        self.allocator = allocator
        self.block_size = int(block_size)
        # 0 = bounded only by the pool itself
        self.capacity_blocks = int(capacity_blocks)
        self._root = hashlib.blake2b(
            f"prefix/{salt}/{block_size}".encode(),
            digest_size=16).digest()
        # digest -> block id, insertion/touch-ordered (last = hottest)
        self._index = OrderedDict()
        self._digest_of = {}            # block id -> digest (evict path)
        self.hits = 0                   # full prompt blocks mapped from
        self.misses = 0                 # ... / not found at admission
        self.insertions = 0
        self.evictions = 0
        self.cow_forks = 0

    # ----------------------------------------------------------- hashing
    @property
    def root_digest(self):
        return self._root

    def chain_digest(self, parent, tokens, position_base):
        h = hashlib.blake2b(digest_size=16)
        h.update(self._root if parent is None else parent)
        h.update(np.asarray(tokens, np.int64).tobytes())
        h.update(int(position_base).to_bytes(8, "little", signed=False))
        return h.digest()

    # ------------------------------------------------------------ lookup
    def _walk(self, tokens, touch):
        """Longest chain of FULL blocks of ``tokens`` present in the
        index: ``(block_ids, digests)``. ``touch`` refreshes LRU."""
        bs = self.block_size
        blocks, digests = [], []
        parent = self._root
        for j in range(len(tokens) // bs):
            d = self.chain_digest(parent, tokens[j * bs:(j + 1) * bs],
                                  j * bs)
            b = self._index.get(d)
            if b is None:
                break
            if touch:
                self._index.move_to_end(d)
            blocks.append(b)
            digests.append(d)
            parent = d
        return blocks, digests

    def lookup(self, tokens):
        """Admission walk (LRU-touching). Returns the matched leading
        ``(block_ids, digests)`` — counters are booked separately via
        :meth:`record_lookup` once the admission actually lands, so a
        blocked FCFS head retrying every iteration doesn't inflate the
        hit rate."""
        return self._walk(tokens, touch=True)

    def match_blocks(self, tokens) -> int:
        """Pure peek (no LRU touch, no counters): how many leading full
        blocks of ``tokens`` this cache holds. The router's
        prefix-affinity signal."""
        return len(self._walk(tokens, touch=False)[0])

    def record_lookup(self, hit_blocks, full_blocks):
        self.hits += hit_blocks
        self.misses += max(0, full_blocks - hit_blocks)

    # ------------------------------------------------------------ insert
    def insert(self, parent, tokens, position_base, block) -> bytes:
        """Register one FULL block under its chain digest and take the
        index's reference. Returns the digest (the caller threads it as
        the next block's parent). A digest already resident keeps its
        existing block (first writer wins — later identical blocks are
        NOT swapped in, so live sharers never see a remap); over
        capacity the LRU tail is reclaimed first, and when nothing is
        reclaimable the insert is skipped (never steals live blocks)."""
        d = self.chain_digest(parent, tokens, position_base)
        if d in self._index:
            self._index.move_to_end(d)
            return d
        if block == 0:
            raise BlockAllocatorError(
                "prefix-index insert of the reserved null block 0")
        if self.capacity_blocks and len(self._index) >= self.capacity_blocks:
            if self.reclaim(
                    len(self._index) - self.capacity_blocks + 1) == 0:
                return d        # bound holds; chain digest still valid
        self.allocator.share([block], owner=self.OWNER)
        self._index[d] = block
        self._digest_of[block] = d
        self.insertions += 1
        return d

    # ---------------------------------------------------------- eviction
    def resident_blocks(self) -> int:
        return len(self._index)

    def reclaimable_blocks(self) -> int:
        """Resident blocks whose ONLY reference is the index's own."""
        rc = self.allocator.refcount
        return sum(1 for b in self._index.values() if rc(b) == 1)

    def shared_blocks(self) -> int:
        """Resident blocks currently mapped by at least one request —
        the ``serving_prefix_blocks_shared`` gauge."""
        rc = self.allocator.refcount
        return sum(1 for b in self._index.values() if rc(b) > 1)

    def reclaim(self, n: int) -> int:
        """Drop up to ``n`` cold cache-only entries (LRU first),
        returning their blocks to the free list. Entries still mapped by
        a request are skipped — reclaim never breaks a live table. The
        scheduler calls this BEFORE preempting anyone: a cold cached
        block is free capacity, a preemption is recompute debt."""
        if n <= 0:
            return 0
        freed = 0
        for d in list(self._index):
            if freed >= n:
                break
            b = self._index[d]
            if self.allocator.refcount(b) != 1:
                continue        # a request still maps it
            del self._index[d]
            del self._digest_of[b]
            self.allocator.free([b], owner=self.OWNER)
            self.evictions += 1
            freed += 1
        return freed

    def drop_all(self) -> int:
        """Release every cache-only entry (teardown / leak checks)."""
        return self.reclaim(len(self._index))

    def stats(self):
        total = self.hits + self.misses
        return {
            "resident_blocks": len(self._index),
            "reclaimable_blocks": self.reclaimable_blocks(),
            "shared_blocks": self.shared_blocks(),
            "hits": self.hits,
            "misses": self.misses,
            "hit_rate": round(self.hits / total, 6) if total else 0.0,
            "insertions": self.insertions,
            "evictions": self.evictions,
            "cow_forks": self.cow_forks,
            "capacity_blocks": self.capacity_blocks,
        }


class PagedKVCache:
    """Device block pools + the traced write.

    Each pool is ONE array of token rows, blocks major, the layer folded
    into the block index (layer ``l``'s block ``b`` is row ``l*N + b``):

    * ``k``/``v``: ``[n_layer*num_blocks, block_size, W]`` in the
      activation dtype, or int8 when ``int8_kv``; ``W`` is
      ``n_head*head_dim`` rounded up to whole 128-lane vregs
      (``row_width``), the pad lanes written as zeros and sliced off
      before any product;
    * ``k_scale``/``v_scale`` (int8 only): ``[n_layer*num_blocks,
      block_size, n_head rounded up to 128]`` fp32 per-row absmax
      scales (``scale_width``), padded the same way;
    * with ``latent_width`` (latent attention, MLA): ONE pool ``kv`` of
      the same shape and no ``v``: a token's row is its normed latent
      and its rotated shared key (``latent_width`` values, 576 -> 640
      lanes), which every head reads as its key and, in its first
      lanes, as its value. ``n_head``/``head_dim`` then describe the
      queries only. Everything that walks the pools (``pool_bytes``,
      ``write_layers``, the block copy, the prefix cache's salt)
      follows the SET of pools and assumes no name.

    Those are the ``paged`` pools. With ``slot_state`` (name -> the shape
    of one slot's state a layer and its dtype, None for the activation
    dtype) the cache also holds ``per_slot`` pools ``[state_layers,
    slots, ...]``: a row a slot and layer, never paged, never shared, and
    updated in place by the layer that owns it (serving/runner.py). The
    block copy and the prefix cache's salt take the paged pools alone;
    ``pool_bytes`` and ``init_pools`` take both kinds (:meth:`pool_kinds`).

    Why this shape: on the TPU an array lives in tiles of 8 sublanes by
    128 lanes (16 rows of bf16), and with ``block_size`` rows of whole
    lanes per block the pool's device layout is plain row-major. The
    step's one write indexes only the two LEADING dimensions and the
    paged loop's read only the first, so the compiler updates and
    gathers the donated pool where it lies. The former ``[L, N, H, BS,
    D]`` pool (minor dimensions 16 x 64: no whole tile) was laid out
    ``{1,4,3,2,0}`` on the device; its read ``pool[layer, ids]`` wanted
    it row-major and its write ``.at[:, blk, :, off, :]`` (a ``:`` over
    layers and heads ahead of the indexed dimensions) wanted
    ``{4,2,3,1,0}``, so both serving programs converted the whole pool
    on the way in and back on the way out: ten pool-sized ``copy`` ops,
    73-80% of device time, 8.1 GB of temporaries beside 4.9 GB of
    arguments at gpt2-medium with 40 slots (PERF.md, PR 27). The device
    pads a 1,600-wide row to 1,664 lanes whatever the shape says; the
    pad in the shape keeps ``pool_bytes`` the bytes the device holds.
    For the scale pools it decides more: at a minor dimension of 16 or
    25 heads the compiler lays them out otherwise and copies them whole
    in every program, at 128 it does not.
    """

    def __init__(self, n_layer, n_head, head_dim, block_size, num_blocks,
                 dtype=jnp.float32, int8_kv=False, latent_width=0,
                 slot_state=None, state_layers=0, slots=0):
        if latent_width and int8_kv:
            raise NotImplementedError(
                "int8 latent pools are not served: a latent row has no "
                "heads to scale by (kv_cache_dtype 'int8' is the K/V "
                "layout's)")
        self.n_layer = n_layer
        self.n_head = n_head
        self.head_dim = head_dim
        self.latent_width = int(latent_width)
        self.row_width = -(-(self.latent_width or n_head * head_dim)
                           // _LANES) * _LANES
        self.scale_width = -(-n_head // _LANES) * _LANES
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.int8_kv = bool(int8_kv)
        self.dtype = jnp.int8 if int8_kv else dtype
        self.slot_state = {
            name: ((int(state_layers), int(slots)) + tuple(shape),
                   dtype if state_dtype is None else state_dtype)
            for name, (shape, state_dtype) in (slot_state or {}).items()}
        self.allocator = BlockAllocator(num_blocks)
        # shared-prefix index (None = prefix caching off). The scheduler
        # reads this attribute; the server attaches it from the
        # serving.prefix_cache config block.
        self.prefix_cache = None

    def attach_prefix_cache(self, capacity_blocks=0):
        """Arm shared-prefix reuse: the salt folds in everything that
        makes two bit-identical token prefixes produce different block
        BYTES (KV dtype, the set of pools, block size), so a cache can
        never serve a block written under a different layout."""
        self.prefix_cache = PrefixCache(
            self.allocator, self.block_size,
            capacity_blocks=capacity_blocks,
            salt="/".join([jnp.dtype(self.dtype).name]
                          + sorted(name for name, kind
                                   in self.pool_kinds().items()
                                   if kind == "paged")))
        return self.prefix_cache

    # -------------------------------------------------- pool construction
    def _pool_shapes(self):
        rows = (self.n_layer * self.num_blocks, self.block_size)
        if self.latent_width:
            return {"kv": (rows + (self.row_width,), self.dtype)}
        shapes = {"k": (rows + (self.row_width,), self.dtype),
                  "v": (rows + (self.row_width,), self.dtype)}
        if self.int8_kv:
            shapes["k_scale"] = (rows + (self.scale_width,), jnp.float32)
            shapes["v_scale"] = (rows + (self.scale_width,), jnp.float32)
        return {**shapes, **self.slot_state}

    def pool_kinds(self) -> dict:
        """Pool name -> ``paged`` (token rows in blocks) or ``per_slot``
        (one row a slot and layer)."""
        return {name: "per_slot" if name in self.slot_state else "paged"
                for name in self._pool_shapes()}

    def init_pools(self, sharding=None):
        """Zeroed device pools; pass through the jitted step and thread
        the returned (donated) pools back in. Two leaves (four with int8
        KV; two more with per-slot state), whatever the depth: the
        dispatch call does not grow with the layer count."""
        pools = {name: jnp.zeros(shape, dtype)
                 for name, (shape, dtype) in self._pool_shapes().items()}
        # COMMIT the arrays (to the caller's sharding — the server passes
        # a mesh-replicated one matching the engine params): a donated
        # program's outputs are committed, and feeding a committed pool
        # to a program first traced on uncommitted inputs is a silent
        # (and large) recompile on the second step
        return jax.device_put(
            pools, sharding if sharding is not None
            else jax.local_devices()[0])

    def pool_bytes(self, kind=None) -> int:
        """HBM the pools occupy (for the serving metrics), the pad lanes
        of ``row_width`` included: all of them, or those of one kind."""
        kinds = self.pool_kinds()
        return sum(int(np.prod(shape)) * jnp.dtype(dtype).itemsize
                   for name, (shape, dtype) in self._pool_shapes().items()
                   if kind in (None, kinds[name]))

    def layer_rows(self, block_ids, first_layer=0, n_layers=1):
        """Pool rows of ``block_ids`` in ``n_layers`` consecutive layers:
        ``[n_layers, *block_ids.shape]`` int32."""
        layers = first_layer + np.arange(n_layers, dtype=np.int32)
        return (layers * self.num_blocks).reshape(
            (-1,) + (1,) * jnp.ndim(block_ids)) + block_ids

    # ------------------------------------------------------- traced write
    @jax.named_scope("kv_write")
    def write_layers(self, pools, new, block_ids, offsets):
        """Write one step's rows for the first ``n`` layers in ONE scatter
        per pool.

        new: pool name -> ``[n, B, ...]`` (``k``/``v``: ``[n, B, H, D]``;
        the latent pool ``kv``: ``[n, B, latent_width]``; decode: a token
        per slot; prefill or verify: the ``B`` positions of a chunk);
        block_ids/offsets: ``[B]`` int32 (the scheduler routes inactive
        slots / pad positions to the null block 0). The runner defers
        every layer's write to one call at the end of the step (``n`` =
        the layers it ran: all of them, or the self-draft's prefix, whose
        K/V are bit-identical to the target's for those layers).

        The scatter indexes the pool's two leading dimensions only (the
        folded row ``layer*N + block`` and the offset) and the update is
        ``[n*B, W]``, untransposed: that is what lets the TPU compiler
        update the donated pool in place (class docstring)."""
        n, B = next(iter(new.values())).shape[:2]
        rows = self.layer_rows(block_ids, n_layers=n).reshape(-1)
        offs = jnp.broadcast_to(offsets, (n, B)).reshape(-1)

        def token_rows(x, width):   # [n, B, ...] -> [n*B, width], 0-padded
            x = x.reshape(n * B, -1)
            return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))

        out = dict(pools)
        for name, rows_new in new.items():
            if self.int8_kv:
                rows_new, scale = quantize_kv(rows_new)   # scales [n, B, H]
                out[name + "_scale"] = pools[name + "_scale"].at[
                    rows, offs].set(token_rows(scale, self.scale_width))
            out[name] = pools[name].at[rows, offs].set(token_rows(
                rows_new.astype(pools[name].dtype), self.row_width))
        return out

    # ------------------------------------------------------- host helpers
    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache positions."""
        return max(1, -(-int(n_tokens) // self.block_size))

    def table_array(self, block_tables, max_blocks, n_rows=None):
        """Host block tables (lists of ids) -> padded ``[B, MB]`` int32
        np array, null-block padded; ``None`` rows (empty slots) are all
        null."""
        if n_rows is None:
            n_rows = len(block_tables)
        out = np.zeros((n_rows, max_blocks), np.int32)
        for i, tbl in enumerate(block_tables):
            if tbl:
                out[i, :len(tbl)] = tbl
        return out
