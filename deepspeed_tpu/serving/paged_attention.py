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
  ``[block_size, W]`` rows into VMEM, ``_GROUP`` blocks a group with the
  next group's copies in flight while this one is reduced; rows stay
  ``W`` lanes wide from HBM to the accumulator (the heads are the rows
  of a block-diagonal query, :func:`_decode_kernel`). It replaced the
  jnp walk there, which took 19.6 of a 20.5 ms decode program at
  gpt2-medium with 40 slots: a sixth of the HBM roofline, three fifths
  of the gathered blocks holding no token of their slot (PERF.md,
  PR 30).
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
# KV blocks the decode kernel fetches as one group, the next group's
# copies in flight while this one is reduced; chosen on the chip at both
# benchmark shapes (PERF.md, PR 30)
_GROUP = 8


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
                           v_scale_pool=None, sm_scale=None):
    """One decode token per slot over the paged pools: the kernel where
    :func:`decode_kernel_runs`, else :func:`paged_chunk_attention` at
    ``C = 1``.

    q/k_cur/v_cur: ``[B, H, D]`` (the current token's K/V stay in
    registers — the pool write is deferred); the other arguments as
    :func:`paged_chunk_attention`'s. Returns ``[B, H, D]`` fp32.
    """
    if decode_kernel_runs(k_pool.dtype):
        with jax.named_scope("paged_attention"):
            return _decode_kernel_call(
                q, k_cur, v_cur, first_block, k_pool, v_pool, block_tables,
                past_lens,
                q.shape[-1] ** -0.5 if sm_scale is None else sm_scale)
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


def _decode_kernel(first_ref, bt_ref, len_ref, q_ref, kc_ref, vc_ref,
                   k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, parity_ref, *,
                   sm_scale, head_dim, group):
    """Grid program ``b`` is slot ``b``: it walks the slot's own
    ``ceil(past_len / BS)`` blocks in groups of ``group``, each block one
    copy of a ``[BS, W]`` row block out of the pool in HBM into one of
    two VMEM buffers, the next group (or the next slot's first) in
    flight while this one is reduced.

    Rows stay ``W`` lanes wide throughout. The ``H`` heads are the rows
    of a block-diagonal query ``[Hp, W]`` (row ``h`` holds the query's
    lanes ``h*D..(h+1)*D``, zero elsewhere), so scores are ``[Hp, T]``
    from one matmul against the K rows, and ``P @ V`` is ``[Hp, W]``, of
    which row ``h`` is read in head ``h``'s lanes alone. Pad lanes and
    pad heads meet zeros of the query and are never read back."""
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    BS, W = k_hbm.shape[1:]
    T = group * BS
    Hp = -(-(W // head_dim) // 16) * 16

    def n_blocks(slot):
        return (len_ref[slot] + BS - 1) // BS

    def n_groups(slot):
        return (n_blocks(slot) + group - 1) // group

    def copies(slot, g, buf, go):
        """Start (``go``) or await the copies of ``slot``'s group ``g``
        into buffer ``buf``: only blocks that hold a token."""
        for i in range(group):
            @pl.when(g * group + i < n_blocks(slot))
            def _():
                row = first_ref[0] + bt_ref[slot, g * group + i]
                for s, (pool, dst) in enumerate(((k_hbm, k_buf),
                                                 (v_hbm, v_buf))):
                    copy = pltpu.make_async_copy(
                        pool.at[row], dst.at[buf, pl.ds(i * BS, BS)],
                        sems.at[s, buf])
                    if go:
                        copy.start()
                    else:
                        copy.wait()

    @pl.when(b == 0)
    def _():
        # a block the walk does not fetch keeps what its buffer held: a
        # masked score gives it weight 0, and 0 x NaN would still be NaN
        parity_ref[0] = 0
        k_buf[...] = jnp.zeros_like(k_buf)
        v_buf[...] = jnp.zeros_like(v_buf)

    parity = parity_ref[0]
    # the slot before starts this slot's first group, unless it walked
    # nothing itself
    @pl.when((b == 0) | (n_groups(jnp.maximum(b - 1, 0)) == 0))
    def _():
        copies(b, 0, parity, True)

    length, ng = len_ref[b], n_groups(b)
    row = jax.lax.broadcasted_iota(jnp.int32, (Hp, W), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (Hp, W), 1)
    own = (lane >= row * head_dim) & (lane < (row + 1) * head_dim)
    qf = q_ref[0].astype(jnp.float32)                       # [1, W]
    q_heads = jnp.where(own, qf, 0.0).astype(q_ref.dtype)   # [Hp, W]

    def body(g, carry):
        m, l, acc = carry
        buf = (parity + g) % 2

        @pl.when(g + 1 < ng)
        def _():
            copies(b, g + 1, 1 - buf, True)

        @pl.when((g + 1 == ng) & (b + 1 < n_slots))
        def _():
            copies(b + 1, 0, 1 - buf, True)

        copies(b, g, buf, False)
        s = _dot_f32(q_heads, k_buf[buf],
                     (((1,), (1,)), ((), ()))) * sm_scale   # [Hp, T]
        col = g * T + jax.lax.broadcasted_iota(jnp.int32, (Hp, T), 1)
        s = jnp.where(col < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + _dot_f32(p, v_buf[buf],
                                     (((1,), (0,)), ((), ())))
        return m_new, l_new, acc

    m, l, acc = jax.lax.fori_loop(
        0, ng, body, (jnp.full((Hp, 1), NEG_INF, jnp.float32),
                      jnp.zeros((Hp, 1), jnp.float32),
                      jnp.zeros((Hp, W), jnp.float32)))
    parity_ref[0] = (parity + ng) % 2
    # fold the current token (always self-visible, so l can never be 0)
    s_cur = jnp.sum(jnp.where(own, qf * kc_ref[0].astype(jnp.float32), 0.0),
                    axis=1, keepdims=True) * sm_scale       # [Hp, 1]
    m_f = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_f)
    p_cur = jnp.exp(s_cur - m_f)
    l = l * alpha + p_cur
    acc = acc * alpha + p_cur * vc_ref[0].astype(jnp.float32)
    o_ref[0] = jnp.sum(jnp.where(own, acc / l, 0.0), axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("sm_scale", "group", "interpret"))
def _decode_kernel_call(q, k_cur, v_cur, first_block, k_pool, v_pool,
                        block_tables, past_lens, sm_scale, group=_GROUP,
                        interpret=False):
    """:func:`_decode_kernel` over ``B`` slots: the pools stay in HBM
    unblocked, the tables and lengths go in by scalar prefetch. So does
    the layer's first row, and the call is a jit of its own: every layer
    of a program is then one traced and lowered kernel, where 24 inlined
    ones added 12 s to each start of a server (PERF.md, PR 30)."""
    B, H, D = q.shape
    BS, W = k_pool.shape[1:]

    def lane_rows(x):           # [B, H, D] -> [B, 1, W], zero pad lanes
        return jnp.pad(x.reshape(B, 1, H * D),
                       ((0, 0), (0, 0), (0, W - H * D)))

    row_spec = pl.BlockSpec((1, 1, W), lambda b, *_: (b, 0, 0))
    pool_spec = pl.BlockSpec(memory_space=pl.ANY)
    out = pl.pallas_call(
        functools.partial(_decode_kernel, sm_scale=sm_scale, head_dim=D,
                          group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[row_spec, row_spec, row_spec, pool_spec, pool_spec],
            out_specs=row_spec,
            scratch_shapes=[pltpu.VMEM((2, group * BS, W), k_pool.dtype),
                            pltpu.VMEM((2, group * BS, W), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, 1, W), jnp.float32),
        # slots in order on one core: each starts the next one's copies
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="paged_decode",
        interpret=interpret,
    )(jnp.asarray(first_block, jnp.int32).reshape(1),
      block_tables.astype(jnp.int32), past_lens.astype(jnp.int32),
      lane_rows(q), lane_rows(k_cur), lane_rows(v_cur), k_pool, v_pool)
    return out[:, 0, :H * D].reshape(B, H, D)


@jax.named_scope("paged_attention")
def paged_chunk_attention(q, k_chunk, v_chunk, first_block, k_pool, v_pool,
                          block_tables, past_lens, *, k_scale_pool=None,
                          v_scale_pool=None, sm_scale=None):
    """The one jnp walk: ``C`` queries PER SLOT over each slot's PAST
    pages plus the chunk itself (registers, causal).

    Every trip gathers one block for each of the ``B`` slots,
    ``ceil(max(past_lens) / BS)`` trips, and a column at or past a slot's
    ``past_len`` is masked; the chunk's own K/V never come from the pool
    (its write is deferred, so a rejected speculative suffix never has to
    be undone on-device). ``C = 1`` is a decode step, one slot a prefill
    chunk (whose pad-tail queries produce rows that are discarded),
    ``C = K+1`` a speculative verify.

    q/k_chunk/v_chunk: ``[B, H, C, D]`` (query c sits at absolute
    position ``past_lens[b] + c``); pools: the ``[L*N, BS, W]`` row
    arrays; first_block: the pool row of this layer's block 0
    (``layer * num_blocks``); block_tables: ``[B, MB]`` int32; past_lens:
    ``[B]`` int32 tokens ALREADY in the pool. Returns ``[B, H, C, D]``
    fp32. One slot may come without its batch dimension (``[H, C, D]``,
    ``[MB]``, a scalar): the compiler does not drop a unit batch
    dimension from these products by itself (runner.py says what it
    cost).
    """
    H, C, D = q.shape[-3:]
    BS = k_pool.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32)
    lens = past_lens[..., None, None, None]
    n_blocks = ((jnp.max(past_lens) + BS - 1) // BS).astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        rows = first_block + block_tables[..., i]
        kb = _read_blocks(k_pool, k_scale_pool, rows, H, D)  # [B,BS,H,D]
        vb = _read_blocks(v_pool, v_scale_pool, rows, H, D)
        s = jnp.einsum("...hcd,...shd->...hcs", qf, kb) * sm_scale
        col = i * BS + jnp.arange(BS, dtype=jnp.int32)
        s = jnp.where(col < lens, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] \
            + jnp.einsum("...hcs,...shd->...hcd", p, vb)
        return m_new, l_new, acc

    m0 = jnp.full(q.shape[:-1], NEG_INF, jnp.float32)
    l0 = jnp.zeros(q.shape[:-1], jnp.float32)
    a0 = jnp.zeros(q.shape, jnp.float32)
    m_p, l_p, a_p = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    # intra-chunk causal piece from registers: key e visible to query c
    # iff e <= c; every query sees itself, so l can never be 0
    s_in = jnp.einsum("...hcd,...hed->...hce", qf,
                      k_chunk.astype(jnp.float32)) * sm_scale
    causal = jnp.arange(C)[:, None] >= jnp.arange(C)[None, :]
    s_in = jnp.where(causal, s_in, NEG_INF)
    m_in = jnp.max(s_in, axis=-1)
    p_in = jnp.exp(s_in - m_in[..., None])
    l_in = jnp.sum(p_in, axis=-1)
    a_in = jnp.einsum("...hce,...hed->...hcd", p_in,
                      v_chunk.astype(jnp.float32))
    # the two online-softmax partials cover disjoint key sets
    m = jnp.maximum(m_p, m_in)
    w_p, w_in = jnp.exp(m_p - m), jnp.exp(m_in - m)
    l = l_p * w_p + l_in * w_in
    acc = a_p * w_p[..., None] + a_in * w_in[..., None]
    return acc / l[..., None]
