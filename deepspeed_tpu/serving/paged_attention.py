"""Streaming paged attention — work scales with LIVE tokens, not capacity.

The runner's ``gather`` impl materialises each slot's block table into a
contiguous ``[B, H, T_max, D]`` view and hands it to the
ops/transformer/decode.py kernel. That composes with the Pallas TPU
kernel, but it reads (and copies) the *allocated* window every step: a
request 40 tokens into a 2048-token capacity still pays 2048 columns of
gather+attention traffic — and decode is KV-bandwidth bound, so that tax
is the whole step.

This module is the PagedAttention-shaped alternative (SOSP '23): a
flash-style online-softmax loop over KV *blocks* with a DYNAMIC trip
count — ``ceil(max_past_len / block_size)`` is a traced scalar, so XLA
lowers the ``fori_loop`` to a while loop whose iterations touch only
blocks that actually hold tokens. One block gather per iteration
(``[B, block_size, W]`` token rows, consumed immediately — never a
full-window materialisation), one compiled program regardless of how
lengths evolve.

The functions attend over the PAST pool only and fold the current
token/chunk from registers (an extra online-softmax term / an intra-chunk
causal piece merged in). That lets the runner defer every layer's KV
write into ONE scatter per pool per step (kv_cache.write_layers). The
pools are the row-shaped arrays of kv_cache.PagedKVCache, read as
``pool[first_block + ids]``: a gather on the leading dimension alone,
which the TPU compiler serves from the donated pool where it lies. (The
former ``pool[layer, ids]`` on a ``[L, N, H, BS, D]`` pool had every
program convert the whole pool to row-major first, and the stacked write
convert it twice more — PERF.md, PR 27.) The int8 KV layout dequantises
per block from the per-row scale pools; the current token stays in
registers at full precision (it is quantised only when written, exactly
like the flax decode path, which attends to the quantised value from the
NEXT step on).

Each function traces under ``jax.named_scope("paged_attention")`` (and the
step's KV write under ``"kv_write"``): trace-time only, the name a profile's
operations carry in their ``tf_op`` stat.

Both impls are selectable per engine (``serving.attention_impl``) and
pinned equal by tests/unit/test_serving.py.
"""

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _read_blocks(pool, scale_pool, rows, H, D):
    """``pool[rows]`` as float32 ``[*rows.shape, BS, H, D]``: the pad
    lanes past ``H*D`` sliced off, int8 rows dequantised by their
    per-row scales."""
    kb = pool[rows][..., :H * D]
    kb = kb.reshape(kb.shape[:-1] + (H, D)).astype(jnp.float32)
    if scale_pool is not None:
        kb = kb * scale_pool[rows][..., :H, None]
    return kb


def _merge(m1, l1, a1, m2, l2, a2):
    """Combine two online-softmax partials over disjoint key sets."""
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m)
    w2 = jnp.exp(m2 - m)
    return m, l1 * w1 + l2 * w2, a1 * w1[..., None] + a2 * w2[..., None]


@jax.named_scope("paged_attention")
def paged_decode_attention(q, k_cur, v_cur, first_block, k_pool, v_pool,
                           block_tables, past_lens, *, k_scale_pool=None,
                           v_scale_pool=None, sm_scale=None):
    """One decode token per slot over the paged pools.

    q/k_cur/v_cur: ``[B, H, D]`` (the current token's K/V stay in
    registers — the pool write is deferred); pools: the
    ``[L*N, BS, W]`` row arrays; first_block: the pool row of this
    layer's block 0 (``layer * num_blocks``); block_tables: ``[B, MB]``
    int32; past_lens: ``[B]`` int32 tokens ALREADY in the pool. Returns
    ``[B, H, D]`` fp32.
    """
    B, H, D = q.shape
    BS = k_pool.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32)
    n_blocks = ((jnp.max(past_lens) + BS - 1) // BS).astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        rows = first_block + block_tables[:, i]        # [B]
        kb = _read_blocks(k_pool, k_scale_pool, rows, H, D)  # [B,BS,H,D]
        vb = _read_blocks(v_pool, v_scale_pool, rows, H, D)
        s = jnp.einsum("bhd,bshd->bhs", qf, kb) * sm_scale
        col = i * BS + jnp.arange(BS, dtype=jnp.int32)
        s = jnp.where(col[None, None, :] < past_lens[:, None, None],
                      s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("bhs,bshd->bhd", p, vb)
        return m_new, l_new, acc

    m0 = jnp.full((B, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H), jnp.float32)
    a0 = jnp.zeros((B, H, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    # fold the current token (always self-visible, so l can never be 0)
    s_cur = jnp.einsum("bhd,bhd->bh", qf,
                       k_cur.astype(jnp.float32)) * sm_scale
    m_f = jnp.maximum(m, s_cur)
    alpha = jnp.exp(m - m_f)
    p_cur = jnp.exp(s_cur - m_f)
    l = l * alpha + p_cur
    acc = acc * alpha[..., None] \
        + p_cur[..., None] * v_cur.astype(jnp.float32)
    return acc / l[..., None]


@jax.named_scope("paged_attention")
def paged_verify_attention(q, k_chunk, v_chunk, first_block, k_pool, v_pool,
                           block_tables, past_lens, *, k_scale_pool=None,
                           v_scale_pool=None, sm_scale=None):
    """Speculative verify: ``C = K+1`` queries PER SLOT over each slot's
    PAST pages plus the candidate chunk itself (registers, causal).

    The batched cross of the two functions above: decode's ``[B, MB]``
    block tables and per-slot ``past_lens``, prefill's multi-position
    chunk with the intra-chunk causal piece merged from registers. One
    program verifies K drafted tokens for every slot in a single target
    forward — the pool writes stay deferred, so a rejected suffix never
    has to be undone on-device.

    q/k_chunk/v_chunk: ``[B, H, C, D]`` (query c sits at absolute
    position ``past_lens[b] + c``); block_tables: ``[B, MB]`` int32;
    past_lens: ``[B]`` int32 tokens ALREADY in the pool. Returns
    ``[B, H, C, D]`` fp32.
    """
    B, H, C, D = q.shape
    BS = k_pool.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32)
    n_blocks = ((jnp.max(past_lens) + BS - 1) // BS).astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        rows = first_block + block_tables[:, i]        # [B]
        kb = _read_blocks(k_pool, k_scale_pool, rows, H, D)  # [B,BS,H,D]
        vb = _read_blocks(v_pool, v_scale_pool, rows, H, D)
        s = jnp.einsum("bhcd,bshd->bhcs", qf, kb) * sm_scale
        col = i * BS + jnp.arange(BS, dtype=jnp.int32)
        s = jnp.where(col[None, None, None, :]
                      < past_lens[:, None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] \
            + jnp.einsum("bhcs,bshd->bhcd", p, vb)
        return m_new, l_new, acc

    m0 = jnp.full((B, H, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, C), jnp.float32)
    a0 = jnp.zeros((B, H, C, D), jnp.float32)
    m_p, l_p, a_p = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    # intra-chunk causal piece from registers: candidate e visible to
    # query c iff e <= c; query 0 always sees itself, so l can never be 0
    s_in = jnp.einsum("bhcd,bhed->bhce", qf,
                      k_chunk.astype(jnp.float32)) * sm_scale
    causal = (jnp.arange(C)[:, None] >= jnp.arange(C)[None, :])
    s_in = jnp.where(causal[None, None], s_in, NEG_INF)
    m_in = jnp.max(s_in, axis=-1)
    p_in = jnp.exp(s_in - m_in[..., None])
    l_in = jnp.sum(p_in, axis=-1)
    a_in = jnp.einsum("bhce,bhed->bhcd", p_in,
                      v_chunk.astype(jnp.float32))
    _, l, acc = _merge(m_p, l_p, a_p, m_in, l_in, a_in)
    return acc / l[..., None]


@jax.named_scope("paged_attention")
def paged_prefill_attention(q, k_chunk, v_chunk, first_block, k_pool, v_pool,
                            bt_row, pos, start, *, k_scale_pool=None,
                            v_scale_pool=None, sm_scale=None):
    """Chunk attention for ONE slot: ``C`` queries at positions ``pos``
    (= start + 0..C-1) over the slot's PAST pages plus the chunk itself
    (registers, causal) — the chunk's pool write is deferred.

    q/k_chunk/v_chunk: ``[H, C, D]``; bt_row: ``[MB]`` int32; start:
    traced scalar, tokens already in the pool. Returns ``[H, C, D]``
    fp32.
    """
    H, C, D = q.shape
    BS = k_pool.shape[1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    qf = q.astype(jnp.float32)
    n_blocks = ((start + BS - 1) // BS).astype(jnp.int32)

    def body(i, carry):
        m, l, acc = carry
        row = first_block + bt_row[i]
        kb = _read_blocks(k_pool, k_scale_pool, row, H, D)   # [BS, H, D]
        vb = _read_blocks(v_pool, v_scale_pool, row, H, D)
        s = jnp.einsum("hcd,shd->hcs", qf, kb) * sm_scale
        col = i * BS + jnp.arange(BS, dtype=jnp.int32)
        s = jnp.where(col[None, None, :] < start, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum("hcs,shd->hcd", p, vb)
        return m_new, l_new, acc

    m0 = jnp.full((H, C), NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, C), jnp.float32)
    a0 = jnp.zeros((H, C, D), jnp.float32)
    m_p, l_p, a_p = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, a0))
    # intra-chunk causal piece from registers: key e visible to query c
    # iff e <= c (pad-tail queries produce garbage that is discarded)
    s_in = jnp.einsum("hcd,hed->hce", qf,
                      k_chunk.astype(jnp.float32)) * sm_scale
    causal = jnp.arange(C)[None, :, None] >= jnp.arange(C)[None, None, :]
    s_in = jnp.where(causal, s_in, NEG_INF)
    m_in = jnp.max(s_in, axis=-1)
    p_in = jnp.exp(s_in - m_in[..., None])
    l_in = jnp.sum(p_in, axis=-1)
    a_in = jnp.einsum("hce,hed->hcd", p_in, v_chunk.astype(jnp.float32))
    _, l, acc = _merge(m_p, l_p, a_p, m_in, l_in, a_in)
    return acc / l[..., None]
