"""Decode attention over a KV cache — the generative-inference hot op.

TPU-native equivalent of the reference's fused KV-cache attention
(`softmax_context_*` in csrc/transformer/inference/csrc/pt_binding.cpp:829
and the attention core of csrc/transformer/inference/csrc/softmax.cu): one
query token per sequence attends to a linear KV cache of valid length
``cache_len``. The reference hand-manages a global KV workspace
(inference/includes/context.h); here the cache is a pair of [B, H, T, D]
jax arrays owned by the model's flax "cache" collection, and this kernel
only reads them.

Design notes (TPU):
* grid over B*H; the single query row is replicated to an (8, D) tile so
  the score GEMM is MXU/VPU tile-aligned (one wasted factor of 8 on a
  bandwidth-bound op — the kernel streams K/V once, which is the actual
  cost at decode time).
* the per-sequence ``cache_len`` vector is scalar-prefetched into SMEM
  whole (a rank-1 SMEM *block* of one element is refused by Mosaic's
  128-tiling rule); the kv loop runs ``cdiv(len, block_k)`` iterations,
  so per-token work scales with the *live* cache length, not the
  allocated cache size.
* off-TPU the mathematically identical masked jnp path runs (also the
  parity oracle in tests/unit/test_inference.py).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deepspeed_tpu.ops._platform import interpret as _interpret
from deepspeed_tpu.ops.transformer.attention import mha_reference

NEG_INF = -1e30
QROWS = 8  # sublane tile height; the 1 live query row is replicated into it
BLOCK_K = 512  # kv tile length (sublane dim of the K/V blocks)


def aligned_cache_len(n_positions: int) -> int:
    """Cache allocation size that avoids the per-step pad copy in
    decode_attention: a BLOCK_K multiple when larger than one block, else
    a 16-multiple (one whole block of any sublane-tileable size)."""
    if n_positions > BLOCK_K:
        return -(-n_positions // BLOCK_K) * BLOCK_K
    return -(-n_positions // 16) * 16


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, *, sm_scale,
                   block_k, n_head, quantized=False, ks_ref=None,
                   vs_ref=None):
    # len_ref: the whole [B] length vector (scalar prefetch); program
    # b*H + h serves sequence b
    length = len_ref[pl.program_id(0) // n_head]
    q = q_ref[0]  # [QROWS, D]

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        s = jax.lax.dot_general(q, k.astype(q.dtype),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if quantized:
            # int8 cache: one absmax scale per cached row (the reference's
            # int8 dequant, csrc/transformer/inference/csrc/dequantize.cu)
            # folds into the score/value matmuls column-wise. Scales ride
            # the LANE dim ([1, 1, T] blocks): a [T, 1] layout pads each
            # row to 128 lanes and streams 128x the scale bytes.
            ks = ks_ref[0, 0, pl.ds(j * block_k, block_k)]      # [BK]
            s = s * ks[None, :]
        cols = j * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (QROWS, block_k), 1)
        s = jnp.where(cols < length, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=1)
        if quantized:
            vs = vs_ref[0, 0, pl.ds(j * block_k, block_k)]      # [BK]
            # int8 magnitudes (≤127) are exact in bf16, so the value
            # matmul runs at full bf16 MXU rate like the fp path
            pv = (p * vs[None, :]).astype(jnp.bfloat16)
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                pv, v.astype(jnp.bfloat16), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        else:
            acc = acc * alpha[:, None] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        return acc, m_new, l_new

    d = q.shape[-1]
    acc = jnp.zeros((QROWS, d), jnp.float32)
    m = jnp.full((QROWS,), NEG_INF, jnp.float32)
    l = jnp.zeros((QROWS,), jnp.float32)
    acc, m, l = jax.lax.fori_loop(0, pl.cdiv(length, block_k), body,
                                  (acc, m, l))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, k_scale=None,
                     v_scale=None, sm_scale=None, use_flash=None):
    """softmax(q·K[:len]ᵀ)·V[:len] for one decode step.

    q: [B, H, 1, D]; k_cache/v_cache: [B, H, T, D] (T = allocated cache);
    cache_len: int32 scalar — or a [B] vector of PER-SEQUENCE valid
    lengths, the continuous-batching form where every slot of the static
    batch sits at its own position (serving/ gathers each slot's pages
    into the contiguous [B, H, T, D] view this op reads). The current
    token's K/V must already be written. With ``k_scale``/``v_scale``
    ([B, H, T] fp32 per-row scales) the caches are int8 and dequant folds
    into the kernel's matmuls (the reference's int8 path,
    csrc/transformer/inference/csrc/dequantize.cu). Returns [B, H, 1, D].
    """
    B, H, Sq, D = q.shape
    assert Sq == 1, f"decode_attention takes one query token, got {Sq}"
    quantized = k_scale is not None
    assert quantized == (v_scale is not None)
    T = k_cache.shape[2]
    lens = jnp.asarray(cache_len, jnp.int32)
    assert lens.ndim in (0, 1), (
        f"cache_len must be a scalar or a [B] vector, got {lens.shape}")
    if lens.ndim == 1:
        assert lens.shape[0] == B, (
            f"per-sequence cache_len has {lens.shape[0]} entries for "
            f"batch {B}")
    if sm_scale is None:
        sm_scale = D ** -0.5
    if use_flash is None:
        from deepspeed_tpu.ops.transformer.attention import _flash_available
        use_flash = _flash_available()
    if not use_flash:
        k, v = k_cache, v_cache
        if quantized:
            k = (k.astype(jnp.float32) * k_scale[..., None]).astype(q.dtype)
            v = (v.astype(jnp.float32) * v_scale[..., None]).astype(q.dtype)
        if lens.ndim == 1:
            mask = jnp.arange(T)[None, None, None, :] \
                < lens[:, None, None, None]
        else:
            mask = (jnp.arange(T) < lens)[None, None, None, :]
        return mha_reference(q, k, v, causal=False,
                             sm_scale=sm_scale, mask=mask)

    # pad the cache dim to a block multiple rather than shrinking the
    # block (a tiny divisor of an odd T would serialise the kv loop);
    # padded columns sit beyond cache_len, so the mask already kills them.
    # This copies the whole cache — callers on the hot path should allocate
    # aligned_cache_len(T) so Tp == T and the pad is a no-op (the model's
    # flax cache does; see models/gpt2.py).
    block_k = min(T, BLOCK_K)
    Tp = -(-T // block_k) * block_k
    if Tp != T:
        pad = [(0, 0), (0, 0), (0, Tp - T), (0, 0)]
        k_cache = jnp.pad(k_cache, pad)
        v_cache = jnp.pad(v_cache, pad)
        if quantized:
            pad2 = [(0, 0), (0, 0), (0, Tp - T)]
            k_scale = jnp.pad(k_scale, pad2)
            v_scale = jnp.pad(v_scale, pad2)
    qf = jnp.broadcast_to(q.reshape(B * H, 1, D), (B * H, QROWS, D))
    kf = k_cache.reshape(B * H, Tp, D)
    vf = v_cache.reshape(B * H, Tp, D)
    # one length per sequence: a scalar broadcasts to every sequence, so
    # the per-sequence path costs nothing extra
    len_arr = jnp.broadcast_to(lens, (B,))

    # index maps take the scalar-prefetch ref as a trailing argument
    cache_spec = pl.BlockSpec((1, Tp, D), lambda b, lens: (b, 0, 0))
    scale_spec = pl.BlockSpec((1, 1, Tp), lambda b, lens: (b, 0, 0))
    q_spec = pl.BlockSpec((1, QROWS, D), lambda b, lens: (b, 0, 0))
    in_specs = [q_spec, cache_spec, cache_spec]
    operands = [len_arr, qf, kf, vf]
    if quantized:
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale.reshape(B * H, 1, Tp).astype(jnp.float32),
                     v_scale.reshape(B * H, 1, Tp).astype(jnp.float32)]

        def kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref):
            _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                           sm_scale=sm_scale, block_k=block_k, n_head=H,
                           quantized=True, ks_ref=ks_ref, vs_ref=vs_ref)
    else:
        kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                                   block_k=block_k, n_head=H)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B * H,),
            in_specs=in_specs,
            out_specs=q_spec),
        out_shape=jax.ShapeDtypeStruct((B * H, QROWS, D), q.dtype),
        interpret=_interpret(),
    )(*operands)
    return out[:, :1, :].reshape(B, H, 1, D)


# ------------------------------------------------------- int8 KV cache path
def quantize_kv(kv):
    """Per-row absmax int8 quantization of new K/V entries: [B, H, S, D]
    -> (int8 values, fp32 scales [B, H, S]). The reference stores fp16
    KV and int8 weights; an int8 KV cache is the TPU-side extension that
    halves cache HBM (dequant folds into the decode matmuls)."""
    absmax = jnp.max(jnp.abs(kv.astype(jnp.float32)), axis=-1)
    scale = absmax / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(kv.astype(jnp.float32) / safe[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, jnp.where(scale == 0.0, 0.0, safe)


def decode_attention_quantized(q, k_int, k_scale, v_int, v_scale, cache_len,
                               *, sm_scale=None, use_flash=None):
    """softmax(q·dequant(K)[:len]ᵀ)·dequant(V)[:len] over an int8 cache —
    the named entry point for the int8 form of :func:`decode_attention`."""
    return decode_attention(q, k_int, v_int, cache_len, k_scale=k_scale,
                            v_scale=v_scale, sm_scale=sm_scale,
                            use_flash=use_flash)
