"""Flash attention — Pallas TPU kernels with a custom VJP.

The TPU-native replacement for the reference's attention core: the
softmax kernels (csrc/transformer/softmax_kernels.cu), the attention-score
strided-batch GEMMs (csrc/includes/strided_batch_gemm.h) and the attn
``attn_dropout_checkpoint`` memory knobs of the fused transformer layer
(csrc/transformer/ds_transformer_cuda.cpp). Online-softmax tiling keeps
memory O(seq) instead of O(seq^2) — the kernel never materialises the
[S, S] score matrix, which is what lets the TPU build run the long-context
configs (SURVEY.md §5.7) densely where the reference needed block-sparsity.

Layout: [batch, heads, seq, head_dim]; fp32 accumulators in VMEM. TWO
kernel forms per pass, dispatched on sequence length (_use_streaming):
resident (≤ 4096: full K/V staged per program, causal skip via the loop
bound — ~11% faster at 1024) and streaming (beyond: K/V blocks stream
through the innermost grid axis with scratch accumulators — O(block)
VMEM, unbounded seq; the resident form VMEM-OOMs at 8192). The resident
backward is ONE kernel, ``flash_dkv``, that writes dq, dk and dv from each
score tile once; shapes whose dq does not fit beside it in VMEM
(_fused_bwd_fits) keep the two-call form, ``flash_dq`` + ``flash_dkv``,
as does the streaming backward.

All kernels run in interpret mode off-TPU so CPU tests exercise the same
code path bit-for-bit (tests/unit/test_flash.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._platform import interpret as _interpret

NEG_INF = -1e30
LOG2E = 1.4426950408889634
LANES = 8  # replication width for per-row stats (lse/delta) — see _fwd_kernel


def _apply_causal_mask(s, row0, col0, block_q, block_k, offset):
    """Mask score block s ([BQ, BK] at rows row0.., cols col0..) so row r
    only attends keys <= r + offset (offset = Sk - Sq, decode suffix)."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    return jnp.where(cols <= rows + offset, s, NEG_INF)


# --------------------------------------------------------------------- forward
#
# All three kernels STREAM their long axis through the grid (kv blocks
# for fwd/dq, q blocks for dkv) with fp32 VMEM scratch accumulators that
# persist across the innermost grid axis — so per-program VMEM is
# O(block), independent of sequence length. The previous design staged
# the full K/V (resp. Q) per program, which VMEM-OOMed at seq 8192.
# Causal blocks entirely above the diagonal skip their compute via
# pl.when (the block fetch still pipelines — bandwidth, not FLOPs).
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, sm_scale, causal, block_q, block_k, num_kv, offset):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: kv block j intersects rows [qi*BQ, (qi+1)*BQ) only if its
    # first key column is <= the block's last row + offset
    live = (j * block_k <= (qi + 1) * block_q - 1 + offset) \
        if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]  # [BQ, D] native dtype — bf16 operands keep the MXU
        # at full rate; accumulation is f32 via preferred_element_type
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qi * block_q, j * block_k,
                                   block_q, block_k, offset)

        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(j == num_kv - 1)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        # lse is replicated over LANES trailing lanes so the 2D-per-row
        # value satisfies the TPU (8, 128)-tile constraint (same trick as
        # jax's own flash kernel, which pads to 128; 8 keeps it small)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(l_safe))[:, None], (block_q, LANES))


# -------------------------------------------------------------------- backward
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc_ref, *, sm_scale, causal, block_q, block_k, num_kv,
               offset):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    live = (j * block_k <= (qi + 1) * block_q - 1 + offset) \
        if causal else True

    @pl.when(live)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]      # [BQ, 1] (lane-replicated stats)
        delta = delta_ref[0, :, 0:1]
        k = k_ref[0]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qi * block_q, j * block_k,
                                   block_q, block_k, offset)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_kv - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc_ref, dv_acc_ref, *, sm_scale, causal,
                block_q, block_k, num_q, offset):
    kj = pl.program_id(1)
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)

    # causal: q block i reaches kv block kj only if its last row + offset
    # is >= the kv block's first key column
    live = ((i + 1) * block_q - 1 + offset >= kj * block_k) \
        if causal else True

    @pl.when(live)
    def _compute():
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]      # [BQ, 1]
        delta = delta_ref[0, :, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, i * block_q, kj * block_k,
                                   block_q, block_k, offset)
        p = jnp.exp(s - lse)                                # [BQ, BK]
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(i == num_q - 1)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)


# ---------------- resident variants (seq <= _RESIDENT_MAX_SEQ) -----------
# The full K/V (resp. Q) is staged in VMEM per program and the kv loop
# runs inside the kernel with the causal loop-bound skip. ~11% faster
# than the streaming form at seq 1024 (no revisit bubbles, true FLOP
# skip), but VMEM is O(seq) so it caps out; measured good through 4096.

def _fwd_kernel_resident(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale, causal,
                block_q, block_k, seq_k, offset):
    qi = pl.program_id(1)
    q = q_ref[0]  # [BQ, D] native dtype — bf16 operands keep the MXU at
    # full rate; accumulation is f32 via preferred_element_type

    num_kv = pl.cdiv(seq_k, block_k)
    if causal:
        # last kv block that intersects rows [qi*BQ, (qi+1)*BQ) after the
        # decode suffix offset (q rows map to keys [0, row + offset])
        num_kv = jnp.minimum(num_kv,
                             pl.cdiv((qi + 1) * block_q + offset, block_k))

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qi * block_q, j * block_k,
                                   block_q, block_k, offset)

        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc = acc * alpha[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    d = q.shape[-1]
    acc = jnp.zeros((block_q, d), jnp.float32)
    m = jnp.full((block_q,), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, num_kv, body, (acc, m, l))

    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    # lse is replicated over LANES trailing lanes so the 2D-per-row value
    # satisfies the TPU (8, 128)-tile constraint (same trick as jax's own
    # flash kernel, which pads to 128; 8 keeps the buffer small)
    lse_ref[0] = jnp.broadcast_to((m + jnp.log(l_safe))[:, None],
                                  (block_q, LANES))


def _dq_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *,
               sm_scale, causal, block_q, block_k, seq_k, offset):
    qi = pl.program_id(1)
    q = q_ref[0]
    do = do_ref[0]
    lse = lse_ref[0, :, 0:1]      # [BQ, 1] (lane-replicated stats)
    delta = delta_ref[0, :, 0:1]

    num_kv = pl.cdiv(seq_k, block_k)
    if causal:
        num_kv = jnp.minimum(num_kv,
                             pl.cdiv((qi + 1) * block_q + offset, block_k))

    def body(j, dq):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, qi * block_q, j * block_k,
                                   block_q, block_k, offset)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        return dq + jax.lax.dot_general(ds.astype(k.dtype), k,
                                        (((1,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)

    dq = jnp.zeros(q.shape, jnp.float32)
    dq = jax.lax.fori_loop(0, num_kv, body, dq)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *, sm_scale, causal, block_q, block_k, seq_q,
                offset):
    kj = pl.program_id(1)
    k = k_ref[0]  # [BK, D]
    v = v_ref[0]

    num_q = pl.cdiv(seq_q, block_q)
    start_q = jnp.int32(0)
    if causal:
        # first q block whose last key index (row + offset) reaches kj*BK
        start_q = jnp.maximum(kj * block_k - offset, 0) // block_q

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), 0:1]      # [BQ, 1]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, i * block_q, kj * block_k,
                                   block_q, block_k, offset)
        p = jnp.exp(s - lse)                                # [BQ, BK]
        dv = dv + jax.lax.dot_general(p.astype(do.dtype), do,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dk = dk + jax.lax.dot_general(ds.astype(q.dtype), q,
                                      (((0,), (0,)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return dk, dv

    dk = jnp.zeros(k.shape, jnp.float32)
    dv = jnp.zeros(v.shape, jnp.float32)
    dk, dv = jax.lax.fori_loop(start_q, num_q, body, (dk, dv))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _bwd_kernel_resident(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dk_ref, dv_ref, dq_acc_ref, *, sm_scale,
                         causal, block_q, block_k, seq_q, offset):
    """dq, dk and dv from ONE pass over the score tiles: the dkv kernel
    above with dq added, so s, p, dP and dS are computed once a tile (the
    dq/dkv pair computes them twice). Program (b, kj) owns kv block kj; dq
    for the whole sequence accumulates over kj in f32 scratch and its
    block, the same for every kj, is written back once per b.

    All three accumulate TRANSPOSED ([D, BK], and [D, Sq] for dq): at head
    64 a [BQ, D] product result fills half of each 128-lane row and pops
    twice the MXU results, and dVᵀ += dOᵀ·p, dKᵀ += Qᵀ·dS, dQᵀ += Kᵀ·dSᵀ
    transpose the small operands where pᵀ·dO and dSᵀ·Q transposed the
    [BQ, BK] tiles (the v5e loop body at 512 x 512: 2,889 -> 2,016
    bundles, and the kernel 0.99 -> 0.78 ms at gpt2-medium's call).
    The products and their operands are the pair's: the gradients are its
    bit for bit on the chip."""
    kj = pl.program_id(1)
    k = k_ref[0]  # [BK, D]
    v = v_ref[0]
    kt = k.T      # [D, BK], once a program

    @pl.when(kj == 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    num_q = pl.cdiv(seq_q, block_q)
    start_q = jnp.int32(0)
    if causal:
        start_q = jnp.maximum(kj * block_k - offset, 0) // block_q

    def body(i, carry):
        dkt, dvt = carry
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        q = q_ref[0, rows, :]
        do = do_ref[0, rows, :]
        lse = lse_ref[0, rows, 0:1]      # [BQ, 1]
        delta = delta_ref[0, rows, 0:1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _apply_causal_mask(s, i * block_q, kj * block_k,
                                   block_q, block_k, offset)
        p = jnp.exp(s - lse)                                # [BQ, BK]
        dvt = dvt + jax.lax.dot_general(do, p.astype(do.dtype),
                                        (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * sm_scale).astype(q.dtype)
        dkt = dkt + jax.lax.dot_general(q, ds, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32)
        # dq^T's columns: a lane slice, static where one block spans Sq
        cols = slice(None) if block_q == seq_q else rows
        dq_acc_ref[:, cols] += jax.lax.dot_general(
            kt, ds, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dkt, dvt

    d = k.shape[-1]
    dkt = jnp.zeros((d, block_k), jnp.float32)
    dvt = jnp.zeros((d, block_k), jnp.float32)
    dkt, dvt = jax.lax.fori_loop(start_q, num_q, body, (dkt, dvt))
    dk_ref[0] = dkt.T.astype(dk_ref.dtype)
    dv_ref[0] = dvt.T.astype(dv_ref.dtype)

    @pl.when(kj == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].T.astype(dq_ref.dtype)


# ------------------------------------------------------------------ dispatch
def _pick_block(seq, streaming=False, target=None):
    if target is None:
        import os
        # measured defaults: 512 for the resident kernels (round-2
        # sweep), 1024 for streaming — bigger blocks amortise the
        # revisit bubbles (seq 8192: 68 -> 90.9 TFLOPS; 2048 VMEM-OOMs).
        # DS_FLASH_BLOCK overrides for sweeps.
        target = int(os.environ.get("DS_FLASH_BLOCK",
                                    "1024" if streaming else "512"))
    b = min(seq, target)
    while seq % b:
        b //= 2
    return max(b, 1)


# Above this many keys/queries the resident kernels' O(seq) VMEM staging
# no longer fits (measured: 4096 good, 8192 OOMs the 16 MB VMEM) and the
# O(block)-VMEM streaming kernels take over (~11% slower at 1024, but
# unbounded in seq). DS_FLASH_STREAM=1 forces streaming everywhere.
_RESIDENT_MAX_SEQ = 4096


def _use_streaming(Sq, Sk):
    import os
    if os.environ.get("DS_FLASH_STREAM", "") == "1":
        return True
    return max(Sq, Sk) > _RESIDENT_MAX_SEQ


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, sm_scale=None):
    out, _ = _flash_fwd(q, k, v, causal, sm_scale)
    return out


def _flash_fwd(q, k, v, causal, sm_scale):
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    stream = _use_streaming(Sq, Sk)
    bq, bk = _pick_block(Sq, stream), _pick_block(Sk, stream)
    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)

    if not stream:
        kernel = functools.partial(
            _fwd_kernel_resident, sm_scale=sm_scale, causal=causal,
            block_q=bq, block_k=bk, seq_k=Sk, offset=Sk - Sq)
        o, lse = pl.pallas_call(
            kernel,
            grid=(B * H, Sq // bq),
            in_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
                pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, bq, D), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, bq, LANES), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32),
            ],
            name="flash_fwd",
            interpret=_interpret(),
        )(qf, kf, vf)
        out = o.reshape(B, H, Sq, D)
        return out, (q, k, v, out, lse)

    num_kv = Sk // bk
    kernel = functools.partial(_fwd_kernel, sm_scale=sm_scale, causal=causal,
                               block_q=bq, block_k=bk, num_kv=num_kv,
                               offset=Sk - Sq)
    o, lse = pl.pallas_call(
        kernel,
        # kv blocks stream through the innermost grid axis; the scratch
        # accumulators carry across it and the output block (same (b, i)
        # for every j) is written on the last visit
        grid=(B * H, Sq // bq, num_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
            pltpu.VMEM((bq, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_fwd",
        interpret=_interpret(),
    )(qf, kf, vf)
    out = o.reshape(B, H, Sq, D)
    return out, (q, k, v, out, lse)


# The one-pass backward stages Q, dO and dq [Sq, D] and dq^T's f32
# accumulator [D, Sq] whole per b, beside the K/V blocks. Compiled for the
# v5e's 16 MiB of scoped VMEM (tests/unit/test_serving_pool_layout.py) it
# fits at every resident length for heads up to 256 while [Sq, D] of the
# operand dtype stays within 3 MiB (f32 4096 x 192); f32 4096 x 256 and
# heads of 512 from 1536 rows do not. Those keep the two-call form, as do
# q blocks that are not whole lane tiles (dq^T is sliced by columns).
_FUSED_BWD_MAX_HEAD = 256
_FUSED_BWD_MAX_ROWS_BYTES = 3 << 20


def _fused_bwd_fits(Sq, D, dtype, block_q):
    return (D <= _FUSED_BWD_MAX_HEAD and
            Sq * D * jnp.dtype(dtype).itemsize <= _FUSED_BWD_MAX_ROWS_BYTES
            and (block_q % 128 == 0 or block_q == Sq))


def _bwd_resident(qf, kf, vf, dof, lse, delta, *, sm_scale, causal,
                  block_q, block_k):
    """The resident backward over [B*H, S, D] operands: ONE call,
    ``flash_dkv``, that writes dq too (grid (B*H, Sk/BK); Q, dO and the row
    stats staged whole per b, K/V a block per program)."""
    BH, Sq, D = qf.shape
    Sk = kf.shape[1]
    return pl.pallas_call(
        functools.partial(
            _bwd_kernel_resident, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=Sq, offset=Sk - Sq),
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sq, D), qf.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), kf.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), vf.dtype),
        ],
        # dq's block and its accumulator carry across the kv axis
        scratch_shapes=[pltpu.VMEM((D, Sq), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="flash_dkv",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)


def _bwd_resident_pair(qf, kf, vf, dof, lse, delta, *, sm_scale, causal,
                       block_q, block_k):
    """The resident backward in two calls, ``flash_dq`` (K/V staged whole,
    a q block per program) and ``flash_dkv`` (Q/dO staged whole, a kv block
    per program), each computing the scores: for shapes whose dq does not
    fit beside the one-pass kernel's staging (:func:`_fused_bwd_fits`)."""
    BH, Sq, D = qf.shape
    Sk = kf.shape[1]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel_resident, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_k=Sk, offset=Sk - Sq),
        grid=(BH, Sq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, Sk, D), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, D), qf.dtype),
        name="flash_dq",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)
    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel_resident, sm_scale=sm_scale, causal=causal,
            block_q=block_q, block_k=block_k, seq_q=Sq, offset=Sk - Sq),
        grid=(BH, Sk // block_k),
        in_specs=[
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, Sq, D), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((1, Sq, LANES), lambda b, j: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, j: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, Sk, D), kf.dtype),
            jax.ShapeDtypeStruct((BH, Sk, D), vf.dtype),
        ],
        name="flash_dkv",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)
    return dq, dk, dv


def _flash_bwd(causal, sm_scale, res, g, g_lse=None):
    q, k, v, out, lse = res
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    B, H, Sq, D = q.shape
    Sk = k.shape[2]
    stream = _use_streaming(Sq, Sk)
    bq, bk = _pick_block(Sq, stream), _pick_block(Sk, stream)

    qf = q.reshape(B * H, Sq, D)
    kf = k.reshape(B * H, Sk, D)
    vf = v.reshape(B * H, Sk, D)
    dof = g.reshape(B * H, Sq, D)
    # delta = rowsum(do * o): the softmax-jacobian correction term,
    # lane-replicated like lse. A direct lse cotangent (ring attention's
    # merge weights differentiate through lse) folds in exactly here:
    # dL/ds_ij = p_ij (dp_ij - delta_i + g_lse_i), since dlse_i/ds_ij=p_ij.
    delta_rows = jnp.sum(
        dof.astype(jnp.float32) *
        out.reshape(B * H, Sq, D).astype(jnp.float32),
        axis=-1, keepdims=True)
    if g_lse is not None:
        delta_rows = delta_rows - g_lse.reshape(B * H, Sq, 1)
    delta = jnp.broadcast_to(delta_rows, (B * H, Sq, LANES))

    if not stream:
        resident = (_bwd_resident if _fused_bwd_fits(Sq, D, q.dtype, bq)
                    else _bwd_resident_pair)
        dq, dk, dv = resident(qf, kf, vf, dof, lse, delta,
                              sm_scale=sm_scale, causal=causal,
                              block_q=bq, block_k=bk)
        return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
                dv.reshape(B, H, Sk, D))

    num_kv = Sk // bk
    num_q = Sq // bq
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_kv=num_kv,
                          offset=Sk - Sq),
        grid=(B * H, num_q, num_kv),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_dq",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, sm_scale=sm_scale, causal=causal,
                          block_q=bq, block_k=bk, num_q=num_q,
                          offset=Sk - Sq),
        # q blocks stream through the innermost axis per kv block
        grid=(B * H, num_kv, num_q),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, bq, LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Sk, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Sk, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_dkv",
        interpret=_interpret(),
    )(qf, kf, vf, dof, lse, delta)

    return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Sk, D),
            dv.reshape(B, H, Sk, D))


flash_attention.defvjp(lambda q, k, v, causal, sm_scale:
                       _flash_fwd(q, k, v, causal, sm_scale),
                       _flash_bwd)


# ------------------------------------------- (out, lse) differentiable form
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention_with_lse(q, k, v, causal=True, sm_scale=None):
    """Flash attention returning ``(out, lse)`` with lse [B, H, Sq] fp32,
    differentiable in BOTH outputs — the building block ring attention's
    online-softmax merge needs (its chunk weights are functions of lse)."""
    (out, lse), _ = _flash_fwd_lse(q, k, v, causal, sm_scale)
    return out, lse


def _flash_fwd_lse(q, k, v, causal, sm_scale):
    out, res = _flash_fwd(q, k, v, causal, sm_scale)
    B, H, Sq, _ = q.shape
    lse = res[4][:, :, 0].reshape(B, H, Sq)
    return (out, lse), res


def _flash_bwd_lse(causal, sm_scale, res, g):
    g_out, g_lse = g
    return _flash_bwd(causal, sm_scale, res, g_out, g_lse=g_lse)


flash_attention_with_lse.defvjp(
    lambda q, k, v, causal, sm_scale: _flash_fwd_lse(q, k, v, causal,
                                                     sm_scale),
    _flash_bwd_lse)
