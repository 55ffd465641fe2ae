"""Block-sparse attention Pallas kernels.

TPU-native replacement for the reference's triton block-sparse stack
(ops/sparse_attention/matmul.py ``_kernel`` :13 — SDD/DSD matmuls,
softmax.py, and the csrc/sparse_attention/utils.cpp LUT builder). The
layout [H, nq, nk] gates a flash-style online-softmax sweep: the kv loop
visits every block but the whole block body is predicated on
``layout[qi, j]``, so Mosaic skips the MXU work for absent blocks — the
TPU analogue of triton's LUT-driven launch. Memory stays O(seq) (no dense
[S, S] scores), which is where the reference's 10-16× longer-sequence
claim comes from (BASELINE.md sparse attention rows).

Backward reuses the same predication with the transposed layout for
dk/dv. All kernels run in interpret mode off-TPU (CPU tests).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._platform import interpret as _interpret

NEG_INF = -1e30
LANES = 8


def _fwd_kernel(layout_ref, kpm_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, block, seq, has_bias):
    qi = pl.program_id(1)
    q = q_ref[0]
    num_kv = seq // block

    def body(j, carry):
        acc, m, l = carry

        def attend(carry):
            acc, m, l = carry
            k = k_ref[0, pl.ds(j * block, block), :]
            v = v_ref[0, pl.ds(j * block, block), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale
            if has_bias:
                # key-padding bias (0 = attend, ~-1e9 = masked): the
                # online softmax self-corrects — masked contributions get
                # weight exp(-1e9 - m_final) == 0 once a valid key raises m
                s = s + kpm_ref[0:1, pl.ds(j * block, block)]
            if causal:
                rows = qi * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                cols = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s = jnp.where(cols <= rows, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1))
            p = jnp.exp(s - m_new[:, None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + jnp.sum(p, axis=1)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc, m_new, l_new

        return jax.lax.cond(layout_ref[0, qi, j] != 0, attend,
                            lambda c: c, carry)

    d = q.shape[-1]
    acc = jnp.zeros((block, d), jnp.float32)
    m = jnp.full((block,), NEG_INF, jnp.float32)
    l = jnp.zeros((block,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kv, body, (acc, m, l))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    empty = l == 0.0  # rows with no attended block at all → zero output
    o_ref[0] = jnp.where(empty[:, None], 0.0, o_ref[0]).astype(o_ref.dtype)
    lse_ref[0] = jnp.broadcast_to(
        (m + jnp.log(l_safe))[:, None], (block, LANES))


def _dq_kernel(layout_ref, kpm_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, *, sm_scale, causal, block, seq,
               has_bias):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]
    delta = delta_ref[0, :, 0:1]
    num_kv = seq // block

    def body(j, dq):
        def attend(dq):
            k = k_ref[0, pl.ds(j * block, block), :]
            v = v_ref[0, pl.ds(j * block, block), :]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale
            if has_bias:
                s = s + kpm_ref[0:1, pl.ds(j * block, block)]
            if causal:
                rows = qi * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                cols = j * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s = jnp.where(cols <= rows, s, NEG_INF)
            p = jnp.exp(s - lse)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            return dq + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        return jax.lax.cond(layout_ref[0, qi, j] != 0, attend,
                            lambda d: d, dq)

    dq = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, num_kv, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(layout_ref, kpm_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, dk_ref, dv_ref, *, sm_scale, causal, block, seq,
                has_bias):
    kj = pl.program_id(1)
    k = k_ref[0]
    v = v_ref[0]
    num_q = seq // block

    def body(i, carry):
        def attend(carry):
            dk, dv = carry
            q = q_ref[0, pl.ds(i * block, block), :]
            do = do_ref[0, pl.ds(i * block, block), :]
            lse = lse_ref[0, pl.ds(i * block, block), 0:1]
            delta = delta_ref[0, pl.ds(i * block, block), 0:1]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32) \
                * sm_scale
            if has_bias:
                s = s + kpm_ref[0:1, pl.ds(kj * block, block)]
            if causal:
                rows = i * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 0)
                cols = kj * block + jax.lax.broadcasted_iota(
                    jnp.int32, (block, block), 1)
                s = jnp.where(cols <= rows, s, NEG_INF)
            p = jnp.exp(s - lse)
            dv = dv + jax.lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ds = p * (dp - delta) * sm_scale
            dk = dk + jax.lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return dk, dv

        # transposed gating: kv block kj is touched by q block i
        return jax.lax.cond(layout_ref[0, i, kj] != 0, attend,
                            lambda c: c, carry)

    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(0, num_q, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def block_sparse_attention(q, k, v, layout, key_padding_bias=None,
                           block=None, causal=False, sm_scale=None):
    """Attention restricted to the block layout.

    q,k,v: [B, H, S, D]; layout: [H, S//block, S//block] int;
    key_padding_bias: optional [B, S] ADDITIVE fp32 score bias
    (0 = attend, ~-1e9 = masked key — the reference's
    key_padding_mask_mode='add')."""
    out, _ = _bs_fwd(q, k, v, layout, key_padding_bias, block, causal,
                     sm_scale)
    return out


def block_sparse_attention_gathered(q, k, v, layout, key_padding_bias=None,
                                    block=None, causal=False, sm_scale=None):
    """Gather-then-dense block-sparse attention — same semantics as
    :func:`block_sparse_attention`, different execution strategy.

    The layout is STATIC, so each q-row-block's live kv blocks are known
    at trace time: a static ``jnp.take`` packs only the live K/V blocks
    into ``[nq, max_live, block, D]`` and dense MXU-shaped einsums run
    over the packed keys — compute and memory scale with the layout
    density (× the per-row ragged-padding to ``max_live``), NOT with
    S². Backward falls out of autodiff (the gather's transpose is the
    scatter-add), so numerics match the predicated-sweep kernel path to
    rounding. Memory: packed K/V is ``density·nq`` × a kv copy — fine for
    the local+global layouts this exists for."""
    B, H, S, D = q.shape
    if block is None:
        block = S // layout.shape[-1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    if isinstance(layout, jax.core.Tracer):
        raise TypeError(
            "block_sparse_attention_gathered needs a CONCRETE layout "
            "(numpy) — the live-block LUT is built at trace time; pass "
            "the sparsity config's numpy layout, not a traced array")
    lay = np.asarray(layout) != 0
    Hh, nq, nk = lay.shape
    assert nq * block == S, (lay.shape, block, S)
    max_live = max(int(lay.sum(axis=-1).max()), 1)
    # static LUT: idx[h, i, t] = t-th live kv block of q-row-block i
    idx = np.zeros((Hh, nq, max_live), np.int32)
    valid = np.zeros((Hh, nq, max_live), bool)
    for h in range(Hh):
        for i in range(nq):
            live = np.nonzero(lay[h, i])[0]
            idx[h, i, :len(live)] = live
            valid[h, i, :len(live)] = True
    idx_j = jnp.asarray(idx)
    # gathered key COLUMN ids per (h, i, t, c): for causal + padding masks
    cols = idx[..., None] * block + np.arange(block)    # [H,nq,L,blk]
    col_ok = np.broadcast_to(valid[..., None], cols.shape)

    def _attend(q, k, v, kpb):
        return _gathered_attend(q, k, v, kpb, idx_j=idx_j, cols=cols,
                                col_ok=col_ok, block=block, causal=causal,
                                sm_scale=sm_scale, max_live=max_live)

    kpb_in = (None if key_padding_bias is None
              else jnp.asarray(key_padding_bias, jnp.float32))
    # remat: the packed [B,H,nq,blk,L,blk] score/weight tensors would
    # otherwise be SAVED for backward across every layer (OOMed at
    # BERT-large seq 2048); recompute-in-backward keeps residency at the
    # inputs, the same trade flash attention makes
    return jax.checkpoint(_attend)(q, k, v, kpb_in)


def _gathered_attend(q, k, v, kpb, *, idx_j, cols, col_ok, block, causal,
                     sm_scale, max_live):
    B, H, S, D = q.shape
    Hh, nq, _ = idx_j.shape
    nk = S // block
    kb = k.reshape(B, H, nk, block, D)
    vb = v.reshape(B, H, nk, block, D)
    # pack live kv blocks: [B, H, nq, L, blk, D] (static gather per head)
    kg = jnp.take_along_axis(
        kb[:, :, None], idx_j[None, :, :, :, None, None], axis=3)
    vg = jnp.take_along_axis(
        vb[:, :, None], idx_j[None, :, :, :, None, None], axis=3)
    qb = q.reshape(B, H, nq, block, D)

    s = jnp.einsum("bhipd,bhilcd->bhiplc", qb, kg,
                   preferred_element_type=jnp.float32) * sm_scale
    neg = jnp.float32(NEG_INF)
    mask = jnp.asarray(col_ok)[None, :, :, None]          # [1,H,nq,1,L,blk]
    if causal:
        rows = (np.arange(nq)[:, None] * block
                + np.arange(block)[None, :])              # [nq, blk]
        cmask = cols[:, :, None, :, :] <= rows[None, :, :, None, None]
        mask = mask & jnp.asarray(cmask)[None]            # [1,H,nq,blk,L,blk]
    s = jnp.where(mask, s, neg)
    if kpb is not None:
        kpb_g = kpb[:, jnp.asarray(cols.reshape(Hh, -1))] \
            .reshape(B, Hh, nq, max_live, block)
        s = s + kpb_g[:, :, :, None]
    sf = s.reshape(B, H, nq, block, max_live * block)
    m = jnp.max(sf, axis=-1, keepdims=True)
    # rows with NO live key (fully masked) must output zeros, not NaN
    p = jnp.exp(sf - jnp.maximum(m, neg / 2))
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p / jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhiplc,bhilcd->bhipd",
                     p.reshape(B, H, nq, block, max_live, block)
                     .astype(vg.dtype), vg,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, S, D).astype(q.dtype)


def _specs(H, block, nq, D, S):
    # the layout LUT lives in SMEM: the kernels read layout[0, qi, j] at a
    # DYNAMIC j, and Mosaic only allows unaligned dynamic scalar loads
    # from scalar memory (a VMEM i32 load must be 128-lane aligned —
    # failed to compile at seq 512). nq^2 i32 is a few KB.
    lay = pl.BlockSpec((1, nq, nq), lambda b, i: (b % H, 0, 0),
                       memory_space=pltpu.SMEM)
    qb = pl.BlockSpec((1, block, D), lambda b, i: (b, i, 0))
    full = pl.BlockSpec((1, S, D), lambda b, i: (b, 0, 0))
    stat = pl.BlockSpec((1, block, LANES), lambda b, i: (b, i, 0))
    statf = pl.BlockSpec((1, S, LANES), lambda b, i: (b, 0, 0))
    return lay, qb, full, stat, statf


def _kpm_arr(key_padding_bias, B, H, S):
    """[B, S] additive bias -> ([B, S] array, spec, has_bias).
    Kept 2D at its natural width — the (8,128) HBM tiling stores it dense,
    and the kernels slice a (1, block) row per key block instead of
    streaming a LANES-wide broadcast (128x the mask bytes). The spec
    shares one bias row across all H heads of a batch (b // H); without a
    mask, a 1-row dummy (never read: the kernels compile the add out when
    has_bias is False) keeps the pallas signature static."""
    if key_padding_bias is None:
        arr = jnp.zeros((1, S), jnp.float32)
        spec = pl.BlockSpec((1, S), lambda b, i: (0, 0))
        return arr, spec, False
    kpb = jnp.asarray(key_padding_bias, jnp.float32)
    assert kpb.shape == (B, S), (kpb.shape, (B, S))
    spec = pl.BlockSpec((1, S), lambda b, i: (b // H, 0))
    return kpb, spec, True


def _bs_fwd(q, k, v, layout, key_padding_bias, block, causal, sm_scale):
    B, H, S, D = q.shape
    if block is None:
        block = S // layout.shape[-1]
    assert layout.shape[-1] * block == S, (layout.shape, block, S)
    if sm_scale is None:
        sm_scale = D ** -0.5
    nq = S // block
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    layout = jnp.asarray(layout, jnp.int32)
    kpm, kpm_spec, has_bias = _kpm_arr(key_padding_bias, B, H, S)

    lay, qb, full, stat, _ = _specs(H, block, nq, D, S)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                          block=block, seq=S, has_bias=has_bias),
        grid=(B * H, nq),
        in_specs=[lay, kpm_spec, qb, full, full],
        out_specs=[qb, stat],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
                   jax.ShapeDtypeStruct((B * H, S, LANES), jnp.float32)],
        interpret=_interpret(),
    )(layout, kpm, qf, kf, vf)
    return o.reshape(B, H, S, D), (q, k, v, layout, key_padding_bias,
                                   o.reshape(B, H, S, D), lse)


def _bs_bwd(block, causal, sm_scale, res, g):
    q, k, v, layout, key_padding_bias, out, lse = res
    B, H, S, D = q.shape
    if block is None:
        block = S // layout.shape[-1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    nq = S // block
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)
    dof = g.reshape(B * H, S, D)
    kpm, kpm_spec, has_bias = _kpm_arr(key_padding_bias, B, H, S)
    delta = jnp.broadcast_to(
        jnp.sum(dof.astype(jnp.float32) *
                out.reshape(B * H, S, D).astype(jnp.float32),
                axis=-1, keepdims=True), (B * H, S, LANES))

    lay, qb, full, stat, statf = _specs(H, block, nq, D, S)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block=block, seq=S, has_bias=has_bias),
        grid=(B * H, nq),
        in_specs=[lay, kpm_spec, qb, full, full, qb, stat, stat],
        out_specs=qb,
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        interpret=_interpret(),
    )(layout, kpm, qf, kf, vf, dof, lse, delta)

    kb = pl.BlockSpec((1, block, D), lambda b, j: (b, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block=block, seq=S, has_bias=has_bias),
        grid=(B * H, nq),
        in_specs=[lay, kpm_spec, full, kb, kb, full, statf, statf],
        out_specs=[kb, kb],
        out_shape=[jax.ShapeDtypeStruct((B * H, S, D), k.dtype),
                   jax.ShapeDtypeStruct((B * H, S, D), v.dtype)],
        interpret=_interpret(),
    )(layout, kpm, qf, kf, vf, dof, lse, delta)

    return (dq.reshape(B, H, S, D), dk.reshape(B, H, S, D),
            dv.reshape(B, H, S, D), None, None)


block_sparse_attention.defvjp(
    lambda q, k, v, layout, kpb, block, causal, sm_scale:
    _bs_fwd(q, k, v, layout, kpb, block, causal, sm_scale),
    _bs_bwd)


def layout_to_dense_mask(layout, block, seq):
    """Expand a block layout to an element mask [H, S, S] (the oracle)."""
    lay = np.asarray(layout)
    return np.kron(lay, np.ones((block, block), dtype=bool))
