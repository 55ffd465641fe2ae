"""Streaming paged attention — work scales with LIVE tokens, not capacity.

The PagedAttention shape (SOSP '23): a flash-style online softmax over KV
*blocks*, touching only blocks that hold tokens, never a materialised
``[B, H, T_max, D]`` window (decode is KV-bandwidth bound, so reading the
allocated window instead of the live one would be the whole step), one
compiled program regardless of how lengths evolve. There are two
implementations and one rule, :func:`decode_kernel_runs`, which chooses
from the platform, the pool's dtype and the mesh's size (no option):

* **Decode (one query a slot) on a TPU, bfloat16 pools, no multi-device
  mesh**: one Pallas kernel call a layer (``paged_decode`` on a profile's
  ``XLA Ops`` line). The pools stay in HBM; each slot walks its OWN
  ``ceil(past_len / block_size)`` blocks, each block one async copy of
  ``[block_size, W]`` rows into VMEM, ``_GROUP`` blocks a group; the
  groups of all slots are one stream, of which ``_AHEAD`` are fetched
  or in flight beside the one being reduced, across slot boundaries;
  rows stay ``W`` lanes wide from HBM to the accumulator (the heads are
  the rows of a block-diagonal query, :func:`_decode_kernel`). It
  replaced the jnp walk there, which took 19.6 of a 20.5 ms decode
  program at gpt2-medium with 40 slots: a sixth of the HBM roofline,
  three fifths of the gathered blocks holding no token of their slot
  (PERF.md, PR 30).
* **Everything else** (prefill and verify chunks everywhere; decode off
  the TPU, over int8 pools, and under a multi-device mesh, where GSPMD
  refuses a bare ``pallas_call``): :func:`paged_chunk_attention`, ONE
  jnp loop with a DYNAMIC trip count —
  ``ceil(max_past_len / block_size)`` is a traced scalar, so XLA lowers
  it to a while loop; one block gather per iteration
  (``[B, block_size, W]`` token rows, consumed immediately). Decode is
  that walk at ``C = 1`` (the kernel's parity oracle,
  tests/unit/test_paged_decode_kernel.py), a prefill chunk at ``B = 1``,
  a speculative verify at ``C = K+1``.

Both serve two row forms, told apart by the pools they are handed and by
nothing else. **Heads in lanes** (``k`` and ``v`` pools): a row holds
``K`` heads of ``D`` lanes, and query head ``h`` reads the lanes of KV
head ``h // G`` (grouped-query attention, ``G = H / K`` queries a KV
head; multi-head attention is the group of 1). **One latent a token**
(one pool, ``v_pool=None``; MLA with the key's up-projection absorbed
into the query): a row is the token's latent and its rotated shared key,
EVERY head's key, and in its first ``v_width`` lanes every head's value;
a slot's query is ``[H, W']`` dense rows. To the kernel both are the
same product, a ``[Hp, W]`` query matrix against whole ``W``-lane rows:
block-diagonal in the first form, dense in the second, which keeps the
whole ``[H, W]`` result where the first reads each head's diagonal
lanes.

Both attend over the PAST pool only and fold the current token/chunk
from registers (an intra-chunk causal piece merged in). That lets the
runner defer every layer's KV write into ONE scatter per pool per step
(kv_cache.write_layers). The pools are the row-shaped arrays of
kv_cache.PagedKVCache, read as ``pool[first_block + ids]``: a gather (or
the kernel's copies) on the leading dimension alone, which the TPU
compiler serves from the donated pool where it lies. (The former
``pool[layer, ids]`` on a ``[L, N, H, BS, D]`` pool had every program
convert the whole pool to row-major first, and the stacked write convert
it twice more — PERF.md, PR 27.) The int8 KV layout dequantises per block
from the per-row scale pools; the current token stays in registers at
full precision (it is quantised only when written, exactly like the flax
decode path, which attends to the quantised value from the NEXT step on).

Both trace under ``jax.named_scope("paged_attention")`` (and the step's
KV write under ``"kv_write"``): trace-time only, the name a profile's
operations carry in their ``tf_op`` stat.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._platform import interpret as _interpret

NEG_INF = -1e30
# KV blocks the decode kernel fetches and reduces as one group; chosen on
# the chip at both benchmark shapes (PERF.md, PR 30; 16 read again under
# PR 36's schedule: no faster where groups are full, slower where a
# slot's last group is part empty)
_GROUP = 8
# groups of the one stream over all slots that the decode kernel keeps
# fetched or in flight beside the one it reduces (a ring of ``_AHEAD + 1``
# buffers a pool: 2 MB of VMEM at gpt2-medium's row, 3.4 MB at gpt2-xl's);
# chosen on the chip: 2 is 16% slower at medium's mix, 4 no faster
# (PERF.md, PR 36)
_AHEAD = 3


def _read_blocks(pool, scale_pool, rows, H, D):
    """``pool[rows]`` as float32 ``[*rows.shape, BS, H, D]``: the pad
    lanes past ``H*D`` sliced off, int8 rows dequantised by their
    per-row scales."""
    kb = pool[rows][..., :H * D]
    kb = kb.reshape(kb.shape[:-1] + (H, D)).astype(jnp.float32)
    if scale_pool is not None:
        kb = kb * scale_pool[rows][..., :H, None]
    return kb


def decode_kernel_runs(pool_dtype):
    """Whether :func:`paged_decode_attention` is the Pallas kernel here:
    on a TPU, over bfloat16 pools, under no multi-device mesh (GSPMD
    refuses a bare ``pallas_call``). Everywhere else it is the jnp walk.
    The server's block counter asks too, so that it states the walk that
    runs."""
    from deepspeed_tpu.utils import groups
    return (not _interpret() and pool_dtype == jnp.bfloat16
            and not (groups.mesh_is_initialized()
                     and groups.get_mesh().size > 1))


def paged_decode_attention(q, k_cur, v_cur, first_block, k_pool, v_pool,
                           block_tables, past_lens, *, k_scale_pool=None,
                           v_scale_pool=None, sm_scale=None, v_width=None):
    """One decode token per slot over the paged pools: the kernel where
    :func:`decode_kernel_runs`, else :func:`paged_chunk_attention` at
    ``C = 1``.

    q ``[B, H, D]``, k_cur/v_cur ``[B, K, D]`` (``K`` divides ``H``; the
    current token's K/V stay in registers — the pool write is deferred);
    the other arguments as
    :func:`paged_chunk_attention`'s. Returns ``[B, H, D]`` fp32. With
    ``v_pool=None`` (one latent a token): q ``[B, H, W']``, k_cur
    ``[B, W']`` the token's own row, ``v_cur`` unused; returns
    ``[B, H, v_width]``.
    """
    if decode_kernel_runs(k_pool.dtype):
        with jax.named_scope("paged_attention"):
            return _decode_kernel_call(
                q, k_cur, v_cur, first_block, k_pool, v_pool, block_tables,
                past_lens,
                q.shape[-1] ** -0.5 if sm_scale is None else sm_scale,
                v_width=v_width)
    if v_pool is None:
        return paged_chunk_attention(
            q[:, :, None], k_cur[:, None], None, first_block, k_pool, None,
            block_tables, past_lens, sm_scale=sm_scale,
            v_width=v_width)[:, :, 0]
    return paged_chunk_attention(
        q[:, :, None], k_cur[:, :, None], v_cur[:, :, None], first_block,
        k_pool, v_pool, block_tables, past_lens, k_scale_pool=k_scale_pool,
        v_scale_pool=v_scale_pool, sm_scale=sm_scale)[:, :, 0]


def _dot_f32(a, b, dims):
    """``dot_general(a, b)`` to float32 accuracy on the MXU, ``b`` being
    bfloat16 as stored. A bfloat16 ``a`` (the queries) needs one pass:
    every product is exact in float32. A float32 ``a`` (the
    probabilities) is split into three bfloat16 terms that sum to it
    within float32 rounding, stacked on rows so that ``b`` is loaded
    once: nothing is rounded that the jnp loop keeps."""
    if a.dtype == b.dtype:
        return jax.lax.dot_general(a, b, dims,
                                   preferred_element_type=jnp.float32)
    n = a.shape[0]
    terms = []
    for _ in range(3):
        terms.append(a.astype(b.dtype))
        a = a - terms[-1].astype(jnp.float32)
    out = jax.lax.dot_general(jnp.concatenate(terms, axis=0), b, dims,
                              preferred_element_type=jnp.float32)
    return out[:n] + out[n:2 * n] + out[2 * n:]


def _decode_kernel(first_ref, bt_ref, len_ref, next_ref, q_ref, kc_ref,
                   *refs, sm_scale, head_dim, group):
    """Grid program ``b`` reduces slot ``b``; the copies follow ONE
    stream of groups over all slots (slot 0's groups, then slot 1's, ...,
    a slot that holds nothing skipped), and the kernel keeps the next
    ``n_buf - 1`` groups of that stream fetched or in flight beside the
    one being reduced, whatever slot they belong to, in a ring of
    ``n_buf`` VMEM buffers a pool: a slot's last group, its fold of the
    current token and the next slot's start all run with copies queued
    behind them. A group is ``group`` blocks, each block one copy of a
    ``[BS, W]`` row block out of the pool in HBM, and only blocks that
    hold a token are copied. The ring's next buffer to reduce and the
    stream's next group to fetch (slot, group) live in SMEM across grid
    programs; ``next_ref[s]`` is the first slot from ``s`` on that holds
    a token (``n_slots`` past the last).

    A group's turn is ONE basic block (:func:`turn`, one copy of it a
    buffer, chosen by a switch): await the group's copies, reduce it, and
    start the copies of the group ``n_buf - 1`` further down the stream
    into the buffer reduced a turn ago. Each copy is predicated by itself
    (its block holds a token, the stream has not ended) and the cursor
    moves by selects, so no branch stands between the descriptors'
    scalar work (an address out of the table and a bounds check, some 14
    bundles a copy) and the products, and the scheduler runs the one
    under the other; each buffer is an allocation of its own, so that a
    copy into one is seen not to touch the rows read from another. With
    a branch around the starts and one ``[n_buf, T, W]`` allocation the
    vector units sat idle through every descriptor (PERF.md, PR 36).

    Rows stay ``W`` lanes wide throughout, and the queries are the rows
    of a matrix ``[Hp, W]``, so scores are ``[Hp, T]`` from one matmul
    against the K rows and ``P @ V`` is ``[Hp, W]``. With heads in lanes
    (``head_dim`` given; refs: the current V row, the K and V pools, the
    output, a ring a pool) the matrix is block-diagonal: with ``Kr = W //
    D`` lane heads and ``G`` queries a KV head (the query block's rows),
    row ``j*Kr + k`` holds query ``j`` of KV head ``k`` in that head's
    lanes ``k*D..(k+1)*D``, zero elsewhere, and is read back in those
    lanes alone, so that output row ``j`` is the sum of rows ``j*Kr ..
    (j+1)*Kr``; pad lanes and pad heads meet zeros of the query and are
    never read back. With
    one latent a token (``head_dim`` None; refs: the one pool, the
    output, one ring) the matrix is the slot's dense ``[Hp, W]``
    query, the V rows ARE the K rows, and the whole result is kept (the
    caller reads its first lanes). The last three refs of either form:
    the slot's float32 accumulator ``[Hp, W]`` in VMEM, the copies'
    semaphores (one a pool and buffer), the three cursor words in SMEM."""
    latent = head_dim is None
    *refs, acc_ref, sems, state = refs
    n_buf = sems.shape[1]
    if latent:
        k_hbm, o_ref, *k_bufs = refs
        vc_ref, v_bufs = kc_ref, k_bufs
        pools = ((k_hbm, k_bufs),)
    else:
        vc_ref, k_hbm, v_hbm, o_ref, *bufs = refs
        k_bufs, v_bufs = bufs[:n_buf], bufs[n_buf:]
        pools = ((k_hbm, k_bufs), (v_hbm, v_bufs))
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    BS, W = k_hbm.shape[1:]
    T = group * BS
    Hp = acc_ref.shape[0]

    def n_blocks(slot):
        return (len_ref[slot] + BS - 1) // BS

    def copies(slot, g, held, buf, go):
        """Start (``go``) or await the copies of ``slot``'s group ``g``,
        the first ``held`` of its blocks, into buffer ``buf``. One traced
        body, unrolled where it is lowered (``i`` is a constant there):
        written out here, a kernel's eleven calls traced 88 conditionals
        and every engine start paid 2 s for them (PERF.md, PR 36)."""
        def one(i, _):
            @pl.when(i < held)
            def _():
                src = first_ref[0] + bt_ref[slot, g * group + i]
                rows = pl.ds(pl.multiple_of(i * BS, BS), BS)
                for s, (pool, ring) in enumerate(pools):
                    copy = pltpu.make_async_copy(
                        pool.at[src], ring[buf].at[rows], sems.at[s, buf])
                    if go:
                        copy.start()
                    else:
                        copy.wait()

        jax.lax.fori_loop(0, group, one, None, unroll=True)

    def fetch_next(buf):
        """Start the copies of the stream's next group, if it has one,
        into buffer ``buf``, and move the cursor on; no branch."""
        at, g = state[1], state[2]
        slot = jnp.minimum(at, n_slots - 1)
        held = jnp.where(at < n_slots, n_blocks(slot) - g * group, 0)
        copies(slot, g, held, buf, True)
        more = held > group
        state[1] = jnp.where(more, at,
                             next_ref[jnp.minimum(at + 1, n_slots)])
        state[2] = jnp.where(more, g + 1, 0)

    @pl.when(b == 0)
    def _():
        # a block the walk does not fetch keeps what its buffer held: a
        # masked score gives it weight 0, and 0 x NaN would still be NaN
        for _, ring in pools:
            for buffer in ring:
                buffer[...] = jnp.zeros_like(buffer)
        state[0], state[1], state[2] = 0, next_ref[0], 0
        for buf in range(n_buf - 1):
            fetch_next(buf)

    first = state[0]
    length, held = len_ref[b], n_blocks(b)
    ng = (held + group - 1) // group
    if latent:
        q_heads = q_ref[0]                                  # [Hp, W]
        qf = q_heads.astype(jnp.float32)

        def own_lanes(x):
            return x
    else:
        per_kv, kv_rows = q_ref.shape[1], W // head_dim
        row = jax.lax.broadcasted_iota(jnp.int32, (Hp, W), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (Hp, W), 1)
        # row j*Kr + k: query j of KV head k; pad rows own no lane
        head = row if per_kv == 1 else jnp.where(
            row < per_kv * kv_rows, row % kv_rows, kv_rows)
        own = (lane >= head * head_dim) & (lane < (head + 1) * head_dim)
        qf = q_ref[0].astype(jnp.float32)                       # [G, W]
        if per_kv > 1:
            pad = [jnp.zeros((Hp - per_kv * kv_rows, W), jnp.float32)]
            qf = jnp.concatenate(
                [jnp.broadcast_to(qf[j:j + 1], (kv_rows, W))
                 for j in range(per_kv)] + pad[:Hp > per_kv * kv_rows])
        q_heads = jnp.where(own, qf, 0.0).astype(q_ref.dtype)   # [Hp, W]

        def own_lanes(x):
            return jnp.where(own, x, 0.0)

    def turn(buf, g, m, l):
        """The slot's group ``g``, which lies in buffer ``buf``. The
        running max and sum go through the switch; the accumulator stays
        in VMEM, updated where it lies: as an operand, a latent slot's
        ``[128, W]`` (80 vector registers) was copied in and out of every
        arm, a third of the turn, while max and sum read back from VMEM
        put a load at the head of every turn's chain (PERF.md, PR 36)."""
        copies(b, g, held - g * group, buf, False)
        s = _dot_f32(q_heads, k_bufs[buf][...],
                     (((1,), (1,)), ((), ()))) * sm_scale   # [Hp, T]
        col = g * T + jax.lax.broadcasted_iota(jnp.int32, (Hp, T), 1)
        s = jnp.where(col < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        # a slot's ``Hp`` dense query rows make the products the
        # kernel's bound (at the chip's ridge, PERF.md): the
        # probabilities go to the MXU in the pool's dtype, one pass,
        # where a block-diagonal query waits on its copies either way
        # and keeps them whole
        acc_ref[...] = acc_ref[...] * alpha + _dot_f32(
            p.astype(v_bufs[buf].dtype) if latent else p, v_bufs[buf][...],
            (((1,), (0,)), ((), ())))
        fetch_next((buf - 1) % n_buf)
        return m_new, l_new

    def body(g, carry):
        return tuple(jax.lax.switch(
            (first + g) % n_buf,
            [functools.partial(turn, buf) for buf in range(n_buf)],
            g, *carry))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m, l = jax.lax.fori_loop(
        0, ng, body, (jnp.full((Hp, 1), NEG_INF, jnp.float32),
                      jnp.zeros((Hp, 1), jnp.float32)))
    acc = acc_ref[...]
    state[0] = (first + ng) % n_buf
    # fold the current token (always self-visible, so l can never be 0)
    s_cur = jnp.sum(own_lanes(qf * kc_ref[0].astype(jnp.float32)),
                    axis=1, keepdims=True) * sm_scale       # [Hp, 1]
    m_f = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_f)
    p_cur = jnp.exp(s_cur - m_f)
    l = l * alpha + p_cur
    acc = acc * alpha + p_cur * vc_ref[0].astype(jnp.float32)
    if latent:
        o_ref[0] = acc / l
    elif per_kv == 1:
        o_ref[0] = jnp.sum(own_lanes(acc / l), axis=0, keepdims=True)
    else:
        out = own_lanes(acc / l)
        o_ref[0] = jnp.concatenate(
            [jnp.sum(out[j * kv_rows:(j + 1) * kv_rows], axis=0,
                     keepdims=True) for j in range(per_kv)])


@functools.partial(jax.jit, static_argnames=("sm_scale", "group", "ahead",
                                             "interpret", "v_width"))
def _decode_kernel_call(q, k_cur, v_cur, first_block, k_pool, v_pool,
                        block_tables, past_lens, sm_scale, group=_GROUP,
                        ahead=_AHEAD, interpret=False, v_width=None):
    """:func:`_decode_kernel` over ``B`` slots: the pools stay in HBM
    unblocked, the tables and lengths go in by scalar prefetch. So does
    the layer's first row, and the call is a jit of its own: every layer
    of a program is then one traced and lowered kernel, where 24 inlined
    ones added 12 s to each start of a server (PERF.md, PR 30)."""
    B, H = q.shape[:2]
    BS, W = k_pool.shape[1:]
    latent = v_pool is None

    def lane_rows(x, n=1):      # [B, ...] -> [B, n, W], zero pad lanes
        x = x.reshape(B, n, -1)
        return jnp.pad(x, ((0, 0), (0, 0), (0, W - x.shape[-1])))

    row_spec = pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    past_lens = past_lens.astype(jnp.int32)
    # the first slot from each on that holds a token, ``B`` past the last
    walked = jnp.where(past_lens > 0, jnp.arange(B, dtype=jnp.int32), B)
    prefetched = (jnp.asarray(first_block, jnp.int32).reshape(1),
                  block_tables.astype(jnp.int32), past_lens,
                  jnp.append(jax.lax.cummin(walked, reverse=True),
                             jnp.int32(B)))
    # queries a KV head: the query block's rows (heads in lanes)
    per_kv = 1 if latent else H // k_cur.shape[1]
    # the query matrix's rows: whole sublane tiles of heads (of the lanes'
    # heads, pad lanes included, where a row holds them, times the group)
    Hp = -(-(H if latent else W // q.shape[-1] * per_kv) // 16) * 16
    if latent:
        # the slot's queries as dense rows
        q_spec = pl.BlockSpec((1, Hp, W), lambda b, *_: (b, 0, 0))
        rows = (jnp.pad(q, ((0, 0), (0, Hp - H), (0, W - q.shape[-1]))),
                lane_rows(k_cur))
        pool_args, in_specs = (k_pool,), [q_spec, row_spec, pool_spec]
    else:
        D = q.shape[-1]
        q_spec = pl.BlockSpec((1, per_kv, W), lambda b, *_: (b, 0, 0))
        if per_kv > 1:      # row j: query j of every KV head, in its lanes
            q = jnp.swapaxes(q.reshape(B, H // per_kv, per_kv, D), 1, 2)
        rows = (lane_rows(q, per_kv), lane_rows(k_cur), lane_rows(v_cur))
        pool_args = (k_pool, v_pool)
        in_specs = [q_spec, row_spec, row_spec, pool_spec, pool_spec]
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale,
                          head_dim=None if latent else q.shape[-1],
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B,),
            in_specs=in_specs,
            out_specs=q_spec,
            # a ring of ``ahead + 1`` buffers a pool, each an allocation
            # of its own; one DMA semaphore a buffer
            scratch_shapes=[pltpu.VMEM((group * BS, W), pool.dtype)
                            for pool in pool_args
                            for _ in range(ahead + 1)]
            # the slot's accumulator
            + [pltpu.VMEM((Hp, W), jnp.float32),
               pltpu.SemaphoreType.DMA((len(pool_args), ahead + 1)),
               pltpu.SMEM((3,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B,) + q_spec.block_shape[1:],
                                       jnp.float32),
        # slots in order on one core: the copies run ahead across them
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(*prefetched, *rows, *pool_args)
    if latent:
        return out[:, :H, :v_width]
    if per_kv == 1:
        return out[:, 0, :H * D].reshape(B, H, D)
    out = out.reshape(B, per_kv, W // D, D)[:, :, :H // per_kv]
    return jnp.swapaxes(out, 1, 2).reshape(B, H, D)


@jax.named_scope("paged_attention")
def paged_chunk_attention(q, k_chunk, v_chunk, first_block, k_pool, v_pool,
                          block_tables, past_lens, *, k_scale_pool=None,
                          v_scale_pool=None, sm_scale=None, v_width=None):
    """The one jnp walk: ``C`` queries PER SLOT over each slot's PAST
    pages plus the chunk itself (registers, causal).

    Every trip gathers one block for each of the ``B`` slots,
    ``ceil(max(past_lens) / BS)`` trips, and a column at or past a slot's
    ``past_len`` is masked; the chunk's own K/V never come from the pool
    (its write is deferred, so a rejected speculative suffix never has to
    be undone on-device). ``C = 1`` is a decode step, one slot a prefill
    chunk (whose pad-tail queries produce rows that are discarded),
    ``C = K+1`` a speculative verify.

    q ``[B, H, C, D]`` (query c sits at absolute position ``past_lens[b]
    + c``), k_chunk/v_chunk ``[B, K, C, D]`` (query head ``h`` meets KV
    head ``h // (H / K)``); pools: the ``[L*N, BS, W]`` row
    arrays; first_block: the pool row of this layer's block 0
    (``layer * num_blocks``); block_tables: ``[B, MB]`` int32; past_lens:
    ``[B]`` int32 tokens ALREADY in the pool. Returns ``[B, H, C, D]``
    fp32. One slot may come without its batch dimension (``[H, C, D]``,
    ``[MB]``, a scalar): the compiler does not drop a unit batch
    dimension from these products by itself (runner.py says what it
    cost).

    With ``v_pool=None`` the rows are one latent a token (module
    docstring): q ``[B, H, C, W']``, k_chunk ``[B, C, W']`` the chunk's
    own rows, every head's keys, and in their first ``v_width`` lanes its
    values; returns ``[B, H, C, v_width]``. The products take the rows in
    the pool's dtype and accumulate in float32, and a trip gathers
    several blocks a slot, as many keys as the chunk has queries (``H``
    times ``C`` query rows against 16 keys would leave seven eighths of
    the MXU's columns empty, and every trip carries the accumulator
    through HBM).
    """
    H, C, D = q.shape[-3:]
    BS = k_pool.shape[1]
    latent = v_pool is None
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q if latent else q.astype(jnp.float32)
    lens = past_lens[..., None, None, None]
    n_blocks = ((jnp.max(past_lens) + BS - 1) // BS).astype(jnp.int32)
    if latent:
        acc_shape = q.shape[:-1] + (v_width,)
        # as many keys a trip as the chunk has queries (a prefill chunk's
        # past is whole chunks long, so no trip is part empty), and never
        # under ``_GROUP`` blocks: every trip carries the accumulator
        # ``[H, C, v_width]`` through HBM once, 134 MB at 128 heads and a
        # chunk of 512, which at 8 blocks a trip was half of a prefill
        # program (PERF.md, PR 35)
        per_trip = max(_GROUP, C // BS)
        n_trips = (n_blocks + per_trip - 1) // per_trip
        block_tables = jnp.pad(
            block_tables, [(0, 0)] * (block_tables.ndim - 1)
            + [(0, -block_tables.shape[-1] % per_trip)])

        def keys_values(i):
            """The trip's rows ``[.., per_trip*BS, W']`` and their first
            ``v_width`` lanes."""
            ids = jax.lax.dynamic_slice_in_dim(
                block_tables, i * per_trip, per_trip, axis=-1)
            kb = k_pool[first_block + ids]          # [.., g, BS, W]
            kb = kb.reshape(kb.shape[:-3] + (per_trip * BS, -1))[..., :D]
            return kb, kb[..., :v_width]

        def scores(kb):
            return jnp.einsum("...hcd,...sd->...hcs", qf, kb,
                              preferred_element_type=jnp.float32)

        def weighted(p, vb):
            return jnp.einsum("...hcs,...sd->...hcd", p.astype(vb.dtype),
                              vb, preferred_element_type=jnp.float32)
    else:
        acc_shape, per_trip = q.shape, 1
        n_trips = n_blocks
        kv_heads = k_chunk.shape[-3]
        group = H // kv_heads

        def repeat(kv, axis):       # each KV head once for each of its queries
            return kv if group == 1 else jnp.repeat(kv, group, axis=axis)

        k_chunk, v_chunk = repeat(k_chunk, -3), repeat(v_chunk, -3)

        def keys_values(i):
            rows = first_block + block_tables[..., i]
            return (repeat(_read_blocks(k_pool, k_scale_pool, rows,
                                        kv_heads, D), -2),
                    repeat(_read_blocks(v_pool, v_scale_pool, rows,
                                        kv_heads, D), -2))

        def scores(kb):                                 # kb [B,BS,H,D]
            return jnp.einsum("...hcd,...shd->...hcs", qf, kb)

        def weighted(p, vb):
            return jnp.einsum("...hcs,...shd->...hcd", p, vb)

    def body(i, carry):
        m, l, acc = carry
        kb, vb = keys_values(i)
        s = scores(kb) * sm_scale
        col = i * (per_trip * BS) + jnp.arange(per_trip * BS,
                                               dtype=jnp.int32)
        s = jnp.where(col < lens, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + weighted(p, vb)
        return m_new, l_new, acc

    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    a0 = jnp.zeros(acc_shape, jnp.float32)
    m_p, l_p, a_p = jax.lax.fori_loop(0, n_trips, body, (m0, l0, a0))
    # intra-chunk causal piece from registers: key e visible to query c
    # iff e <= c; every query sees itself, so l can never be 0
    if latent:
        s_in = jnp.einsum("...hcd,...ed->...hce", qf, k_chunk,
                          preferred_element_type=jnp.float32) * sm_scale
    else:
        s_in = jnp.einsum("...hcd,...hed->...hce", qf,
                          k_chunk.astype(jnp.float32)) * sm_scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    s_in = jnp.where(causal, s_in, NEG_INF)
    m_in = jnp.max(s_in, axis=-1)
    p_in = jnp.exp(s_in - m_in[..., None])
    l_in = jnp.sum(p_in, axis=-1)
    if latent:
        a_in = jnp.einsum("...hce,...ed->...hcd", p_in.astype(k_chunk.dtype),
                          k_chunk[..., :v_width],
                          preferred_element_type=jnp.float32)
    else:
        a_in = jnp.einsum("...hce,...hed->...hcd", p_in,
                          v_chunk.astype(jnp.float32))
    # the two online-softmax partials cover disjoint key sets
    m = jnp.maximum(m_p, m_in)
    w_p, w_in = jnp.exp(m_p - m), jnp.exp(m_in - m)
    l = l_p * w_p + l_in * w_in
    acc = a_p * w_p[..., None] + a_in * w_in[..., None]
    return acc / l[..., None]
