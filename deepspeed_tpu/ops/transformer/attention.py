"""Multi-head attention ops — dispatcher between the Pallas flash kernel
and a jnp reference.

This is the TPU-native replacement for the reference's fused attention
paths: the softmax/transform kernels inside the training transformer
(``csrc/transformer/softmax_kernels.cu``, ``transform_kernels.cu``) and the
strided-batch-gemm attention core (``csrc/includes/strided_batch_gemm.h``).
On TPU the entire attention block is ONE flash-attention Pallas kernel
(O(seq) memory, online softmax); off-TPU (CPU tests) the mathematically
identical jnp path runs.

Layout convention: ``[batch, heads, seq, head_dim]`` throughout.
"""

from typing import Optional

import jax
import jax.numpy as jnp


def mha_reference(q, k, v, *, causal=True, sm_scale=None, bias=None,
                  mask=None):
    """Plain-XLA attention: the parity oracle and the CPU fallback.

    q,k,v: [B, H, S, D]; bias broadcastable to [B, H, Sq, Sk]; mask is a
    boolean tensor broadcastable to the same (True = keep).
    """
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * sm_scale
    if bias is not None:
        logits = logits + bias.astype(jnp.float32)
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        # offset handles decode where q is a suffix of the kv sequence
        causal_mask = (jnp.arange(sk)[None, :] <=
                       jnp.arange(sq)[:, None] + (sk - sq))
        logits = jnp.where(causal_mask[None, None], logits, -1e30)
    if mask is not None:
        logits = jnp.where(mask, logits, -1e30)
    weights = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights.astype(v.dtype), v)


def _flash_available():
    # effective_platform (not default_backend): code hosted onto the CPU
    # device of a TPU process — e.g. the layered-offload zero_init — must
    # not pick TPU Pallas lowering. On a TPU the kernel is THE path: a
    # flash module that fails to import raises at the call, it does not
    # drop to O(S^2) XLA attention.
    from deepspeed_tpu.ops._platform import effective_platform
    return effective_platform() == "tpu"


def _want_flash(seq_k: int, has_bias: bool, has_mask: bool) -> bool:
    """Default impl choice, measured on one v5e-class chip (PERF.md):
    at seq 128 the flash grid degenerates to one tiny block per (b, h)
    program and XLA's fused O(S^2) attention is 1.35x faster end-to-end
    (BERT-large 211 -> 156 ms/step); at seq 1024 flash wins (GPT-2
    headline). Crossover set at 512 where the fp32 logits buffer also
    starts to matter. ``DS_ATTN_IMPL=flash|xla`` overrides."""
    import os
    impl = os.environ.get("DS_ATTN_IMPL", "").lower()
    if impl == "xla":
        return False
    if impl == "flash":
        return True
    return seq_k >= 512 and not has_bias and not has_mask


def attention(q, k, v, *, causal=True, sm_scale=None, bias=None, mask=None,
              use_flash: Optional[bool] = None):
    """Dispatch: Pallas flash kernel on TPU (long seq), jnp/XLA reference
    otherwise.

    ``use_flash`` forces one path (tests use False for the oracle); env
    ``DS_ATTN_IMPL=flash|xla`` overrides the measured default in
    :func:`_want_flash`."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    if use_flash is None:
        use_flash = _flash_available() and _want_flash(
            k.shape[2], bias is not None, mask is not None)
    if use_flash:
        if bias is not None or mask is not None:
            raise ValueError(
                "the flash kernel has no bias/mask input; drop "
                "DS_ATTN_IMPL=flash / use_flash=True for masked attention")
        from deepspeed_tpu.ops._mesh import map_over_mesh
        from deepspeed_tpu.ops.transformer import flash
        return map_over_mesh(
            lambda q, k, v: flash.flash_attention(q, k, v, causal, sm_scale),
            batch=q.shape[0], heads=q.shape[1])(q, k, v)
    return mha_reference(q, k, v, causal=causal, sm_scale=sm_scale,
                         bias=bias, mask=mask)
