"""Fused block-sparse flash attention — LUT-driven streaming Pallas kernels.

The reference shipped block-sparse attention as a *performance* feature —
up to 6.3x faster and 10-16x longer sequences than its dense attention
(docs/_posts/2020-09-09-sparse-attention.md:28-33, triton LUT kernels in
deepspeed/ops/sparse_attention/matmul.py:13) — while this repo's first two
TPU strategies (predicated sweep, gather-then-dense) ran 2-3x SLOWER than
the repo's own dense flash. This third strategy fuses the static layout
LUT into the streaming flash pipeline:

- each (batch*head) program walks a FLATTENED work list of live
  (q-tile, kv-tile) pairs; the tile indices come from scalar-prefetched
  SMEM LUTs read inside the BlockSpec index_maps, so the pipeline's DMA
  engine fetches exactly the live blocks from HBM — no packed K/V
  materialisation (the gathered impl's cost), no dead-block fetches (the
  predicated impl's cost), and no per-row padding steps (the work list
  is exactly the live pairs, plus one dummy item per empty row so every
  output tile is written);
- compute tiles are MXU-sized (bq x bkc, default 512 x 1024 — the
  measured optimum at block 128, PERF.md) regardless
  of the layout's fine block size; fine-block liveness inside a coarse
  tile is a bit-packed int32 per work item, expanded in-register to a
  score mask (<= 32 fine blocks per coarse tile by construction);
- per-program VMEM is O(tile) via scratch accumulators that reset at
  each q-tile run boundary (begin/end flags), so sequence length is
  unbounded;
- "global" kv columns — attended by (nearly) every row, the killer of
  coarse-tile sparsity in Fixed/BigBird/Longformer layouts — are
  gathered into a contiguous packed region appended after the real
  sequence and fed through the SAME kernel as coarse-dense tiles (the
  per-head bit-masks carry partial liveness; causality in the packed
  region is exact at block level because its diagonal blocks stay in
  the real region). Global ROWS (few) are computed densely in XLA and
  overwrite their output rows.

Wall-clock therefore scales with the layout's live-pair count. Backward
runs the same scheme: dq sweeps the row-major work list, dk/dv sweep the
column-major (transposed) one. The compiler stores per-step block
indices in SMEM (~1 MB), which bounds TOTAL work items per kernel to
~10-20k — the flattened list keeps real layouts far under that.
"""

import functools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._mesh import map_over_mesh
from deepspeed_tpu.ops._platform import interpret as _interpret

NEG_INF = -1e30
LANES = 8
_MAX_BITS = 32   # fine blocks per (q-tile, kv-tile) pair — one int32 word
_F_LIVE = 1      # flags: this step does real work
_F_BEGIN = 2     # flags: first step of its output-tile run (reset scratch)
_F_END = 4       # flags: last step of its run (write the output tile)


# ---------------------------------------------------------------- LUT builder
def _largest_divisor_leq(n, x):
    for d in range(min(n, max(x, 1)), 0, -1):
        if n % d == 0:
            return d
    return 1


def _tile_geometry(nq, nk, blk):
    """Pick (rq, c): fine blocks per compute tile in the q / kv dims.

    bq = rq*blk must divide Sq, bkc = c*blk must divide Skv, and
    rq*c <= 32 so the fine mask of one (q-tile, kv-tile) pair packs into
    one int32."""
    # measured on one v5e chip at seq 8192 blk 128 (PERF.md): (512, 1024)
    # = 1.4-1.5x over dense flash; the rq*c <= 32 budget loop shrinks the
    # kv tile automatically for smaller fine blocks
    bq_target = int(os.environ.get("DS_SPARSE_BQ", "512"))
    bkc_target = int(os.environ.get("DS_SPARSE_BKC", "1024"))
    rq = _largest_divisor_leq(nq, max(1, bq_target // blk))
    c = _largest_divisor_leq(nk, max(1, bkc_target // blk))
    while rq * c > _MAX_BITS:
        if rq >= c and rq > 1:
            rq = _largest_divisor_leq(nq, rq // 2)
        elif c > 1:
            c = _largest_divisor_leq(nk, c // 2)
        else:  # pragma: no cover — rq == c == 1 satisfies the budget
            break
    return rq, c


def _pack_bits(fm, rq, c):
    """[rq, c] bool fine-mask -> one uint32 (bit r*c+cc = fm[r, cc])."""
    b = 0
    for r in range(rq):
        for cc in range(c):
            if fm[r, cc]:
                b |= 1 << (r * c + cc)
    return np.uint32(b)


def _flatten_work(layv, transpose):
    """Build the flattened per-head work list.

    layv: [H, nqc, rq, nkc, c] bool fine layout viewed at tile
    granularity. Returns (own, other, bits, flags, W): own[h, w] is the
    OUTPUT tile index (q tile for fwd/dq, kv tile for dkv), other[h, w]
    the streamed tile; runs over the same output tile are consecutive
    and bracketed by BEGIN/END flags. Output tiles with no live pair get
    one dummy non-LIVE item so their (zero) output is still written.
    Heads with fewer items are padded with non-LIVE repeats of their
    last item (repeat indices = no data movement)."""
    H, nqc, rq, nkc, c = layv.shape
    clive = layv.any(axis=(2, 4))                    # [H, nqc, nkc]
    if transpose:
        clive = clive.transpose(0, 2, 1)             # [H, nkc, nqc]
    n_own = clive.shape[1]
    per_head = []
    for h in range(H):
        items = []                                   # (own, other, bits)
        for i in range(n_own):
            js = np.nonzero(clive[h, i])[0]
            if len(js) == 0:
                items.append((i, 0, np.uint32(0), _F_BEGIN | _F_END))
                continue
            for t, j in enumerate(js):
                fm = (layv[h, j, :, i, :] if transpose
                      else layv[h, i, :, j, :])
                fl = _F_LIVE
                if t == 0:
                    fl |= _F_BEGIN
                if t == len(js) - 1:
                    fl |= _F_END
                items.append((i, j, _pack_bits(fm, rq, c), fl))
        per_head.append(items)
    W = max(len(it) for it in per_head)
    own = np.zeros((H, W), np.int32)
    other = np.zeros((H, W), np.int32)
    bits = np.zeros((H, W), np.uint32)
    flags = np.zeros((H, W), np.int32)
    for h, items in enumerate(per_head):
        for w, (i, j, bb, fl) in enumerate(items):
            own[h, w], other[h, w], bits[h, w], flags[h, w] = i, j, bb, fl
        for w in range(len(items), W):               # tail padding
            own[h, w] = items[-1][0]
            other[h, w] = items[-1][1]
    return own, other, bits.view(np.int32), flags, W


# ------------------------------------------------------------------- kernels
def _fine_mask(s, bits, blk, c, bq, bkc):
    """Apply the bit-packed fine-block mask to score tile s [bq, bkc]."""
    if bq == blk and bkc == blk:
        return s  # one fine block per tile — tile liveness IS the work list
    rows_f = jax.lax.broadcasted_iota(jnp.int32, (bq, bkc), 0)
    cols_f = jax.lax.broadcasted_iota(jnp.int32, (bq, bkc), 1)
    shift = (rows_f // blk) * c + (cols_f // blk)
    live = (jnp.right_shift(bits, shift) & 1) == 1
    return jnp.where(live, s, NEG_INF)


def _scores(q, k, qi, kj, bits, *, sm_scale, causal, blk, c, bq, bkc,
            causal_ntiles):
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    s = _fine_mask(s, bits, blk, c, bq, bkc)
    if causal:
        # packed global-column tiles (kj >= causal_ntiles) carry their
        # causality at block level in the work-list bits — the positional
        # triangle only applies to real-sequence tiles
        rows = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkc), 0)
        cols = kj * bkc + jax.lax.broadcasted_iota(jnp.int32, (bq, bkc), 1)
        s = jnp.where((cols <= rows) | (kj >= causal_ntiles), s, NEG_INF)
    return s


def _fwd_kernel(qi_ref, kj_ref, bits_ref, flags_ref, kpm_ref, q_ref, k_ref,
                v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *, sm_scale,
                causal, blk, c, bq, bkc, H, has_bias, causal_ntiles):
    b = pl.program_id(0)
    w = pl.program_id(1)
    h = b % H
    fl = flags_ref[h, w]

    @pl.when(fl & _F_BEGIN != 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(fl & _F_LIVE != 0)
    def _compute():
        s = _scores(q_ref[0], k_ref[0], qi_ref[h, w], kj_ref[h, w],
                    bits_ref[h, w], sm_scale=sm_scale, causal=causal,
                    blk=blk, c=c, bq=bq, bkc=bkc,
                    causal_ntiles=causal_ntiles)
        if has_bias:
            s = s + kpm_ref[0:1, :]
        m = m_ref[:, 0]
        l = l_ref[:, 0]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        # rows whose every key so far is layout/causal-masked keep
        # m_new == NEG_INF; exp(s - m_new) would be exp(0) == 1 there,
        # so clamp their weights to zero explicitly
        p = jnp.where((m_new <= NEG_INF / 2)[:, None], 0.0,
                      jnp.exp(s - m_new[:, None]))
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(fl & _F_END != 0)
    def _finalize():
        l = l_ref[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0] = jnp.broadcast_to(
            (m_ref[:, 0] + jnp.log(l_safe))[:, None], (bq, LANES))


def _dq_kernel(qi_ref, kj_ref, bits_ref, flags_ref, kpm_ref, q_ref, k_ref,
               v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_acc_ref, *,
               sm_scale, causal, blk, c, bq, bkc, H, has_bias,
               causal_ntiles):
    b = pl.program_id(0)
    w = pl.program_id(1)
    h = b % H
    fl = flags_ref[h, w]

    @pl.when(fl & _F_BEGIN != 0)
    def _init():
        dq_acc_ref[...] = jnp.zeros_like(dq_acc_ref)

    @pl.when(fl & _F_LIVE != 0)
    def _compute():
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        k = k_ref[0]
        s = _scores(q_ref[0], k, qi_ref[h, w], kj_ref[h, w],
                    bits_ref[h, w], sm_scale=sm_scale, causal=causal,
                    blk=blk, c=c, bq=bq, bkc=bkc,
                    causal_ntiles=causal_ntiles)
        if has_bias:
            s = s + kpm_ref[0:1, :]
        # rows with NO live key have lse == NEG_INF; exp(s - lse) would be
        # exp(0) for their masked scores — clamp to zero
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * sm_scale
        dq_acc_ref[...] = dq_acc_ref[...] + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(fl & _F_END != 0)
    def _finalize():
        dq_ref[0] = dq_acc_ref[...].astype(dq_ref.dtype)


def _dkv_kernel(kj_ref, qi_ref, bits_ref, flags_ref, kpm_ref, q_ref, k_ref,
                v_ref, do_ref, lse_ref, delta_ref, *refs, sm_scale, causal,
                blk, c, bq, bkc, H, has_bias, causal_ntiles):
    if has_bias:
        # the additive key-padding bias is a differentiable input: emit
        # its per-(batch*head, key) cotangent as a third output
        (dk_ref, dv_ref, dkpb_ref,
         dk_acc_ref, dv_acc_ref, dkpb_acc_ref) = refs
    else:
        dk_ref, dv_ref, dk_acc_ref, dv_acc_ref = refs
        dkpb_acc_ref = None
    b = pl.program_id(0)
    w = pl.program_id(1)
    h = b % H
    fl = flags_ref[h, w]

    @pl.when(fl & _F_BEGIN != 0)
    def _init():
        dk_acc_ref[...] = jnp.zeros_like(dk_acc_ref)
        dv_acc_ref[...] = jnp.zeros_like(dv_acc_ref)
        if has_bias:
            dkpb_acc_ref[...] = jnp.zeros_like(dkpb_acc_ref)

    @pl.when(fl & _F_LIVE != 0)
    def _compute():
        q = q_ref[0]
        do = do_ref[0]
        lse = lse_ref[0, :, 0:1]
        delta = delta_ref[0, :, 0:1]
        s = _scores(q, k_ref[0], qi_ref[h, w], kj_ref[h, w],
                    bits_ref[h, w], sm_scale=sm_scale, causal=causal,
                    blk=blk, c=c, bq=bq, bkc=bkc,
                    causal_ntiles=causal_ntiles)
        if has_bias:
            s = s + kpm_ref[0:1, :]
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc_ref[...] = dv_acc_ref[...] + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        dsig = p * (dp - delta)     # dL/d(score incl bias): the bias grad
        ds = dsig * sm_scale
        dk_acc_ref[...] = dk_acc_ref[...] + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if has_bias:
            dkpb_acc_ref[0, :] = dkpb_acc_ref[0, :] + jnp.sum(dsig, axis=0)

    @pl.when(fl & _F_END != 0)
    def _finalize():
        dk_ref[0] = dk_acc_ref[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc_ref[...].astype(dv_ref.dtype)
        if has_bias:
            dkpb_ref[0] = dkpb_acc_ref[0, :]


# ---------------------------------------------------------------- public API
class _FusedSparse:
    """One compiled strategy for one (layout, block, causal, tiles) key.

    Holds the numpy work lists and exposes ``attend(q, k, v, kpb)`` — a
    custom-VJP function whose forward/backward all run the LUT-driven
    streaming kernels."""

    def __init__(self, lay, blk, causal, sm_scale, causal_nblocks=None):
        """lay [H, nq, nk] may be RECTANGULAR (nk > nq): kv columns past
        ``causal_nblocks`` fine blocks are packed global columns whose
        causality is already encoded at block level in the layout (the
        positional triangle only applies to the real-sequence prefix)."""
        H, nq, nk = lay.shape
        Sq, Skv = nq * blk, nk * blk
        self.blk, self.causal, self.sm_scale = blk, causal, sm_scale
        self.H, self.Sq, self.Skv = H, Sq, Skv
        rq, c = _tile_geometry(nq, nk, blk)
        if causal_nblocks is None:
            causal_nblocks = nk
        if causal_nblocks != nk:
            # the real/packed boundary must fall on a coarse-tile edge
            c = _largest_divisor_leq(math.gcd(nk, causal_nblocks), c)
        self.bq, self.bkc = rq * blk, c * blk
        self.rq, self.c = rq, c
        assert causal_nblocks % c == 0, (causal_nblocks, c)
        self.causal_ntiles = causal_nblocks // c
        layv = lay.reshape(H, nq // rq, rq, nk // c, c)
        self.nqc, self.nkc = nq // rq, nk // c
        # work lists stay NUMPY: converting here under an active jit trace
        # would cache tracers in this (trace-outliving) object; numpy
        # operands are staged fresh at each pallas_call instead
        (self.qi, self.kj, self.bits,
         self.flags, self.W) = _flatten_work(layv, transpose=False)
        (self.tkj, self.tqi, self.tbits,
         self.tflags, self.Wt) = _flatten_work(layv, transpose=True)
        clive = layv.any(axis=(2, 4))
        self.coarse_density = float(clive.mean())
        self._warned_steps = False

        @jax.custom_vjp
        def attend(q, k, v, kpb):
            out, _ = self._fwd(q, k, v, kpb)
            return out

        attend.defvjp(lambda q, k, v, kpb: self._fwd_res(q, k, v, kpb),
                      functools.partial(self._bwd_impl, with_lse=False))
        self.attend = attend

        @jax.custom_vjp
        def attend_lse(q, k, v, kpb):
            out, lse = self._fwd(q, k, v, kpb)
            B = q.shape[0]
            return out, lse[:, :, 0].reshape(B, self.H, self.Sq)

        def _fwd_res_lse(q, k, v, kpb):
            out, lse = self._fwd(q, k, v, kpb)
            B = q.shape[0]
            pub = lse[:, :, 0].reshape(B, self.H, self.Sq)
            return (out, pub), (q, k, v, kpb, out, lse)

        attend_lse.defvjp(_fwd_res_lse,
                          functools.partial(self._bwd_impl, with_lse=True))
        self.attend_lse = attend_lse

    # kpm helper: the bias block rides the SAME dynamic index as k/v.
    # Prefetch-ref argument order at the index_map is (own, other, bits,
    # flags) = (qi, kj, ...) for fwd/dq and (kj, qi, ...) for dkv — the
    # STREAMED tile is ref index `stream_ref` in both.
    def _kpm(self, kpb, B, kv_is_stream):
        if kpb is None:
            arr = jnp.zeros((1, self.bkc), jnp.float32)
            spec = pl.BlockSpec((1, self.bkc), lambda b, w, *refs: (0, 0))
            return arr, spec, False
        arr = jnp.asarray(kpb, jnp.float32)
        assert arr.shape == (B, self.Skv), (arr.shape, (B, self.Skv))
        H = self.H
        if kv_is_stream:
            spec = pl.BlockSpec(
                (1, self.bkc),
                lambda b, w, own, other, bits, flags:
                (b // H, other[b % H, w]))
        else:
            spec = pl.BlockSpec(
                (1, self.bkc),
                lambda b, w, own, other, bits, flags:
                (b // H, own[b % H, w]))
        return arr, spec, True

    def _specs(self):
        """BlockSpecs shared by the kernels: `own`-indexed q-side tiles
        and `other`-indexed streamed tiles (fwd/dq), or vice versa."""
        H, bq, bkc, D = self.H, self.bq, self.bkc, self._D
        own_q = pl.BlockSpec(
            (1, bq, D),
            lambda b, w, own, other, bits, flags: (b, own[b % H, w], 0))
        own_qstat = pl.BlockSpec(
            (1, bq, LANES),
            lambda b, w, own, other, bits, flags: (b, own[b % H, w], 0))
        own_kv = pl.BlockSpec(
            (1, bkc, D),
            lambda b, w, own, other, bits, flags: (b, own[b % H, w], 0))
        oth_kv = pl.BlockSpec(
            (1, bkc, D),
            lambda b, w, own, other, bits, flags: (b, other[b % H, w], 0))
        oth_q = pl.BlockSpec(
            (1, bq, D),
            lambda b, w, own, other, bits, flags: (b, other[b % H, w], 0))
        oth_qstat = pl.BlockSpec(
            (1, bq, LANES),
            lambda b, w, own, other, bits, flags: (b, other[b % H, w], 0))
        return own_q, own_qstat, own_kv, oth_kv, oth_q, oth_qstat

    def _fwd(self, q, k, v, kpb):
        B, H, Sq, D = q.shape
        Skv = k.shape[2]
        assert (H, Sq, Skv) == (self.H, self.Sq, self.Skv), (
            (H, Sq, Skv), (self.H, self.Sq, self.Skv))
        self._D = D
        # Mosaic stores per-step block indices in SMEM (~1 MB): a work
        # list past ~20k total steps will die inside the compiler with an
        # opaque SMEM OOM — explain it here first
        total = B * H * (2 * self.W + self.Wt)
        if total > 20000 and not self._warned_steps:
            self._warned_steps = True
            from deepspeed_tpu.utils.logging import logger
            logger.warning(
                "fused block-sparse attention: %d total grid steps "
                "(batch %d x heads %d x work lists %d/%d) may exceed the "
                "~1 MB SMEM budget for pipeline block indices; if compile "
                "fails with 'Ran out of memory in memory space smem', use "
                "a denser tile geometry (DS_SPARSE_BQ/DS_SPARSE_BKC), a "
                "bigger sparse block, or DS_SPARSE_IMPL=gathered",
                total, B, H, self.W, self.Wt)
        sm_scale = self.sm_scale if self.sm_scale is not None else D ** -0.5
        bq, bkc = self.bq, self.bkc
        qf = q.reshape(B * H, Sq, D)
        kf = k.reshape(B * H, Skv, D)
        vf = v.reshape(B * H, Skv, D)
        kpm, kpm_spec, has_bias = self._kpm(kpb, B, kv_is_stream=True)
        own_q, own_qstat, _, oth_kv, _, _ = self._specs()
        kernel = functools.partial(
            _fwd_kernel, sm_scale=sm_scale, causal=self.causal,
            blk=self.blk, c=self.c, bq=bq, bkc=bkc, H=H,
            has_bias=has_bias, causal_ntiles=self.causal_ntiles)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B * H, self.W),
            in_specs=[kpm_spec, own_q, oth_kv, oth_kv],
            out_specs=[own_q, own_qstat],
            scratch_shapes=[
                pltpu.VMEM((bq, D), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
                pltpu.VMEM((bq, LANES), jnp.float32),
            ],
        )
        o, lse = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
                jax.ShapeDtypeStruct((B * H, Sq, LANES), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(),
        )(self.qi, self.kj, self.bits, self.flags, kpm, qf, kf, vf)
        return o.reshape(B, H, Sq, D), lse

    def _fwd_res(self, q, k, v, kpb):
        out, lse = self._fwd(q, k, v, kpb)
        return out, (q, k, v, kpb, out, lse)

    def _bwd_impl(self, res, g, with_lse=False):
        if with_lse:
            g, g_lse = g
        else:
            g_lse = None
        q, k, v, kpb, out, lse = res
        B, H, Sq, D = q.shape
        Skv = k.shape[2]
        self._D = D
        sm_scale = self.sm_scale if self.sm_scale is not None else D ** -0.5
        bq, bkc = self.bq, self.bkc
        qf = q.reshape(B * H, Sq, D)
        kf = k.reshape(B * H, Skv, D)
        vf = v.reshape(B * H, Skv, D)
        dof = g.reshape(B * H, Sq, D)
        # softmax-jacobian correction; a direct lse cotangent folds in
        # exactly here (dL/ds_ij = p_ij (dp_ij - delta_i + g_lse_i)),
        # same identity flash.py's _flash_bwd uses
        delta_rows = jnp.sum(
            dof.astype(jnp.float32) *
            out.reshape(B * H, Sq, D).astype(jnp.float32),
            axis=-1, keepdims=True)
        if g_lse is not None:
            delta_rows = delta_rows - g_lse.reshape(B * H, Sq, 1)
        delta = jnp.broadcast_to(delta_rows, (B * H, Sq, LANES))

        own_q, own_qstat, own_kv, oth_kv, oth_q, oth_qstat = self._specs()
        kpm, kpm_spec, has_bias = self._kpm(kpb, B, kv_is_stream=True)
        dq_kernel = functools.partial(
            _dq_kernel, sm_scale=sm_scale, causal=self.causal,
            blk=self.blk, c=self.c, bq=bq, bkc=bkc, H=H,
            has_bias=has_bias, causal_ntiles=self.causal_ntiles)
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B * H, self.W),
            in_specs=[kpm_spec, own_q, oth_kv, oth_kv, own_q, own_qstat,
                      own_qstat],
            out_specs=own_q,
            scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        )
        dq = pl.pallas_call(
            dq_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B * H, Sq, D), q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(),
        )(self.qi, self.kj, self.bits, self.flags, kpm, qf, kf, vf, dof,
          lse, delta)

        kpm2, kpm2_spec, _ = self._kpm(kpb, B, kv_is_stream=False)
        dkv_kernel = functools.partial(
            _dkv_kernel, sm_scale=sm_scale, causal=self.causal,
            blk=self.blk, c=self.c, bq=bq, bkc=bkc, H=H,
            has_bias=has_bias, causal_ntiles=self.causal_ntiles)
        H_ = H
        own_bias = pl.BlockSpec(
            (1, bkc),
            lambda b, w, own, other, bits, flags: (b, own[b % H_, w]))
        out_specs = [own_kv, own_kv] + ([own_bias] if has_bias else [])
        out_shape = [
            jax.ShapeDtypeStruct((B * H, Skv, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Skv, D), v.dtype),
        ] + ([jax.ShapeDtypeStruct((B * H, Skv), jnp.float32)]
             if has_bias else [])
        scratch = [
            pltpu.VMEM((bkc, D), jnp.float32),
            pltpu.VMEM((bkc, D), jnp.float32),
        ] + ([pltpu.VMEM((LANES, bkc), jnp.float32)] if has_bias else [])
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(B * H, self.Wt),
            in_specs=[kpm2_spec, oth_q, own_kv, own_kv, oth_q, oth_qstat,
                      oth_qstat],
            out_specs=out_specs,
            scratch_shapes=scratch,
        )
        outs = pl.pallas_call(
            dkv_kernel,
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=_interpret(),
        )(self.tkj, self.tqi, self.tbits, self.tflags, kpm2, qf, kf, vf,
          dof, lse, delta)
        if has_bias:
            dk, dv, dkpb_bh = outs
            # the bias is shared across the heads of a batch element
            dkpb = dkpb_bh.reshape(B, H, Skv).sum(axis=1).astype(kpb.dtype)
        else:
            dk, dv = outs
            dkpb = None
        return (dq.reshape(B, H, Sq, D), dk.reshape(B, H, Skv, D),
                dv.reshape(B, H, Skv, D), dkpb)


_strategy_cache = {}


def _get_strategy(layout, block, causal, sm_scale, causal_nblocks=None):
    import hashlib
    lay = np.asarray(layout) != 0
    # digest, not raw bytes: sweeps over seq lengths / configs would
    # otherwise retain multi-MB layout keys for the process lifetime
    key = (hashlib.sha256(lay.tobytes()).digest(), lay.shape, block,
           causal, sm_scale, causal_nblocks,
           os.environ.get("DS_SPARSE_BQ", ""),
           os.environ.get("DS_SPARSE_BKC", ""))
    if key not in _strategy_cache:
        _strategy_cache[key] = _FusedSparse(lay, block, causal, sm_scale,
                                            causal_nblocks=causal_nblocks)
    return _strategy_cache[key]


# -------------------------------------------------- layout decomposition
#
# Real layouts (Fixed/BigBird/BSLongformer) are "band + global": a few kv
# columns attended by (nearly) every row and a few q rows attending
# (nearly) everything, over a local band. The global columns make every
# COARSE kv tile live, which erases the kernel's sparsity win (and blows
# the SMEM work-list budget). So the split path PACKS the global columns
# after the real sequence (a few-MB gather) and feeds them through the
# SAME kernel as coarse-dense tiles; global ROWS (few) are computed
# densely in XLA and overwrite their output rows. The decomposition is
# exact for ANY choice of global sets because every part carries its own
# block mask.

def _decompose_layout(lay, causal, col_thresh=0.75, row_thresh=0.75):
    """lay [H, nq, nk] bool -> (gr rows, gc cols, remainder layout).

    A column j is global when its mean liveness over the rows causality
    permits (r >= j when causal) exceeds col_thresh IN ANY HEAD; rows
    symmetrically. Remainder = lay with global rows/cols zeroed."""
    H, nq, nk = lay.shape
    if causal:
        tri = np.tril(np.ones((nq, nk), bool))          # r >= j
        denom_c = np.maximum(tri.sum(axis=0), 1)        # rows >= j
        colness = (lay & tri).sum(axis=1) / denom_c     # [H, nk]
        denom_r = np.maximum(tri.sum(axis=1), 1)        # cols <= r
        rowness = (lay & tri).sum(axis=2) / denom_r     # [H, nq]
    else:
        colness = lay.mean(axis=1)
        rowness = lay.mean(axis=2)
    gc = np.nonzero((colness >= col_thresh).any(axis=0))[0]
    gr = np.nonzero((rowness >= row_thresh).any(axis=0))[0]
    rem = lay.copy()
    rem[:, :, gc] = False
    rem[:, gr, :] = False
    return gr, gc, rem


def _masked_dense_part(q, kg, vg, block_mask, col_ids, row_ids, causal,
                       kpb, sm_scale):
    """Dense masked attention of q rows vs a gathered key subset, with
    per-part normalization: returns (out, lse).

    q [B,H,R,D]; kg/vg [B,H,G,D]; block_mask [H,R,G] bool (element-
    expanded layout); col_ids/row_ids [G]/[R] original token positions
    (causal masking); kpb [B,G] additive bias or None."""
    s = jnp.einsum("bhrd,bhgd->bhrg", q, kg,
                   preferred_element_type=jnp.float32) * sm_scale
    mask = jnp.asarray(block_mask)[None]
    if causal:
        cm = np.asarray(col_ids)[None, :] <= np.asarray(row_ids)[:, None]
        mask = mask & jnp.asarray(cm)[None, None]
    s = jnp.where(mask, s, NEG_INF)
    if kpb is not None:
        s = s + kpb[:, None, None, :]
    m = jnp.max(s, axis=-1)
    # fully-masked rows: zero weights, lse stays NEG_INF
    p = jnp.where((m <= NEG_INF / 2)[..., None], 0.0,
                  jnp.exp(s - m[..., None]))
    l = jnp.sum(p, axis=-1)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhrg,bhgd->bhrd", (p / l_safe[..., None]).astype(
        vg.dtype), vg, preferred_element_type=jnp.float32).astype(q.dtype)
    lse = m + jnp.log(l_safe)
    return out, lse


def _expand_mask(bm, blk):
    """[H, nq, g] block mask -> [H, nq*blk, g*blk] element mask."""
    H, nq, g = bm.shape
    return np.broadcast_to(
        bm[:, :, None, :, None], (H, nq, blk, g, blk)).reshape(
            H, nq * blk, g * blk)


def parse_sparse_mode(mode):
    """'sparse' or 'sparse:<window_tokens>/<block>' -> (window, block).

    ONE home for the defaults (1024/128 — the measured long-seq optimum,
    PERF.md) so the model wiring and bench flop accounting can never
    disagree on what layout a mode string means."""
    bad = ValueError(
        f"sparse attention mode {mode!r}: expected 'sparse' or "
        "'sparse:<window_tokens>/<block>' (e.g. 'sparse:1024/128')")
    if mode == "sparse":
        return 1024, 128
    if not mode.startswith("sparse:"):
        raise bad
    parts = mode.split(":", 1)[1].split("/")
    if len(parts) != 2:
        raise bad
    try:
        win, blk = int(parts[0]), int(parts[1])
    except ValueError:
        raise bad from None
    if blk <= 0 or win <= 0 or win % blk:
        raise ValueError(
            f"sparse attention mode {mode!r}: window {win} must be a "
            f"positive multiple of block {blk}")
    return win, blk


def sparse_mode_layout(mode, num_heads, seq_len):
    """The CAUSAL layout a mode string means — unidirectional Fixed with
    ``window//block`` local blocks + 1 global. Shared by the GPT-2 model
    wiring AND bench.py's flop accounting, so a layout retune can never
    silently desynchronize the two. Returns (layout, block)."""
    from deepspeed_tpu.ops.sparse_attention.sparse_self_attention import \
        get_layout
    from deepspeed_tpu.ops.sparse_attention.sparsity_config import \
        FixedSparsityConfig
    win, blk = parse_sparse_mode(mode)
    if seq_len % blk:
        raise ValueError(
            f"sparse attention mode {mode!r}: sequence length {seq_len} "
            f"must be a multiple of block {blk}")
    layout = get_layout(FixedSparsityConfig(
        num_heads=num_heads, block=blk, num_local_blocks=win // blk,
        num_global_blocks=1, attention="unidirectional"), seq_len)
    return layout, blk


def block_sparse_attention_fused(q, k, v, layout, key_padding_bias=None,
                                 block=None, causal=False, sm_scale=None):
    """LUT-driven streaming block-sparse attention (band + global split).

    Same semantics as ``block_sparse_attention`` (q,k,v [B,H,S,D]; layout
    [H, S//block, S//block]; optional [B,S] ADDITIVE key-padding bias) —
    different execution strategy: see module docstring. The layout must
    be CONCRETE (numpy) — the work lists are built at trace time."""
    if isinstance(layout, jax.core.Tracer):
        raise TypeError(
            "block_sparse_attention_fused needs a CONCRETE layout (numpy) "
            "— the live-block LUTs are built at trace time; pass the "
            "sparsity config's numpy layout, not a traced array")
    B, H, S, D = q.shape
    lay = np.asarray(layout) != 0
    if block is None:
        block = S // lay.shape[-1]
    if sm_scale is None:
        sm_scale = D ** -0.5
    gr, gc, rem = _decompose_layout(lay, causal)
    kpb = (None if key_padding_bias is None
           else jnp.asarray(key_padding_bias, jnp.float32))
    nq = lay.shape[1]

    if len(gc) == 0 and len(gr) == 0:
        strat = _get_strategy(rem, block, causal, sm_scale)
        return map_over_mesh(strat.attend, B)(q, k, v, kpb)

    if len(gc):
        # pack the global columns after the real sequence: per-head
        # liveness (and block-level causality — strictly-below-diagonal
        # blocks only; the diagonal blocks r == j stay in the real
        # region for the positional triangle) rides the work-list bits
        _, c0 = _tile_geometry(nq, nq, block)
        g_pad = -(-len(gc) // c0) * c0
        packed = np.zeros((H, nq, g_pad), bool)
        for t, j in enumerate(gc):
            packed[:, :, t] = lay[:, :, j]
            if causal:
                # rows r < j are fully causal-masked, row r == j needs
                # the positional triangle (stays in the real region)
                packed[:, :j + 1, t] = False
                rem[:, j, j] = lay[:, j, j]
        packed[:, gr, :] = False
        lay2 = np.concatenate([rem, packed], axis=2)
        col_ids = (np.asarray(gc)[:, None] * block
                   + np.arange(block)).reshape(-1)           # [G]
        pad_tok = (g_pad - len(gc)) * block
        strat = _get_strategy(lay2, block, causal, sm_scale,
                              causal_nblocks=nq)
    else:
        strat = _get_strategy(rem, block, causal, sm_scale)
        col_ids, pad_tok = None, 0

    def _attend(q, k, v, kpb):
        if col_ids is not None:
            def _pack(x):
                return jnp.concatenate(
                    [x, x[:, :, col_ids]] +
                    ([jnp.zeros(x.shape[:2] + (pad_tok, x.shape[3]),
                                x.dtype)] if pad_tok else []), axis=2)
            k2, v2 = _pack(k), _pack(v)
            kpb2 = kpb
            if kpb is not None:
                kpb2 = jnp.concatenate(
                    [kpb, kpb[:, col_ids]] +
                    ([jnp.zeros((kpb.shape[0], pad_tok), kpb.dtype)]
                     if pad_tok else []), axis=1)
        else:
            k2, v2, kpb2 = k, v, kpb
        out = strat.attend(q, k2, v2, kpb2)
        if len(gr):
            # the few global rows attend (nearly) everything — dense XLA
            row_ids = (np.asarray(gr)[:, None] * block
                       + np.arange(block)).reshape(-1)       # [R]
            qg = q[:, :, row_ids]
            bm = _expand_mask(lay[:, gr, :], block)           # [H, R, S]
            gout, _ = _masked_dense_part(
                qg, k, v, bm, np.arange(S), row_ids, causal, kpb, sm_scale)
            out = out.at[:, :, row_ids].set(gout)
        return out

    # the dense global-row part's [B,H,R,S] fp32 score tensor must not be
    # saved for backward across every layer — recompute, like flash
    return map_over_mesh(jax.checkpoint(_attend), B)(q, k, v, kpb)
