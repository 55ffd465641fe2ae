"""Grouped quantization kernels.

TPU-native equivalent of csrc/quantization/quantizer.cu (pybind surface
``ds_quantize_fp{32,16}``, ``ds_sr_quantize_*``, asymmetric variants —
csrc/quantization/pt_binding.cpp:62-76) used by MoQ quantize-aware
training (runtime/quantize.py) and the module-quantize injection.

Semantics (matching the CUDA kernel): the tensor is viewed as ``groups``
equal rows; each row is quantized to ``num_bits`` symmetrically (scale =
max|x| / qmax, zero-point-free) or asymmetrically (min/max affine), then
IMMEDIATELY dequantized in place — the reference returns fake-quantized
values in the original dtype, which is what QAT consumes. Stochastic
rounding uses the TPU PRNG (pltpu.prng_random_bits); the CPU fallback uses
counter-based uniforms so tests are deterministic per seed.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _on_tpu():
    from deepspeed_tpu.ops._platform import effective_platform
    return effective_platform() == "tpu"


def _qrange(num_bits, symmetric):
    if symmetric:
        return float(2 ** (num_bits - 1) - 1)
    return float(2 ** num_bits - 1)


def _quantize_rows(x, num_bits, symmetric, stochastic, noise):
    """Shared math: x is [groups, row]; noise in [0,1) same shape or None."""
    xf = x.astype(jnp.float32)
    if symmetric:
        qmax = _qrange(num_bits, True)
        scale = jnp.max(jnp.abs(xf), axis=-1, keepdims=True) / qmax
        scale = jnp.where(scale == 0.0, 1.0, scale)
        q = xf / scale
        if stochastic:
            q = jnp.floor(q + noise)
        else:
            q = jnp.round(q)
        q = jnp.clip(q, -qmax - 1, qmax)
        return q * scale
    qmax = _qrange(num_bits, False)
    lo = jnp.min(xf, axis=-1, keepdims=True)
    hi = jnp.max(xf, axis=-1, keepdims=True)
    scale = (hi - lo) / qmax
    scale = jnp.where(scale == 0.0, 1.0, scale)
    q = (xf - lo) / scale
    if stochastic:
        q = jnp.floor(q + noise)
    else:
        q = jnp.round(q)
    q = jnp.clip(q, 0, qmax)
    return q * scale + lo


def _quant_kernel(seed_ref, x_ref, y_ref, *, num_bits, symmetric, stochastic):
    if stochastic:
        pltpu.prng_seed(seed_ref[0, 0] + pl.program_id(0))
        # int32 bits; the logical shift keeps the top 24 as a non-negative
        # int32 (Mosaic has no uint32 -> float32 cast)
        bits = pltpu.prng_random_bits(x_ref.shape)
        noise = jax.lax.shift_right_logical(bits, 8).astype(jnp.float32) \
            * (1.0 / (1 << 24))
    else:
        noise = None
    y_ref[:] = _quantize_rows(x_ref[:], num_bits, symmetric, stochastic,
                              noise).astype(y_ref.dtype)


_SR_COUNTER = [0]  # fresh noise per call (reference: evolving curand state)


def quantize(x, num_bits=8, groups=1, symmetric=True, stochastic=False,
             seed=None):
    """Fake-quantize ``x`` in-place-semantics (returns same shape/dtype).

    Mirrors ds_[sr_]quantize[_asym]_fp{32,16}: view as [groups, -1] rows,
    per-row scale, round (optionally stochastic), dequantize. When *seed*
    is None, each call draws a fresh seed so stochastic rounding stays
    unbiased across repeated calls."""
    if seed is None:
        _SR_COUNTER[0] += 1
        seed = _SR_COUNTER[0]
    shape, dtype = x.shape, x.dtype
    n = x.size
    assert n % groups == 0, f"numel {n} not divisible by groups {groups}"
    row = n // groups
    xg = x.reshape(groups, row)

    if _on_tpu() and row % 128 == 0 and groups >= 1:
        bg = 1
        # rows per block: <= 1 MiB of fp32, since the in and out blocks
        # are double-buffered beside the kernel's own fp32 temporaries
        # (a 4 MiB block ran out of the 16 MiB scoped VMEM on a v5e)
        while groups % (bg * 2) == 0 and bg * 2 * row <= (1 << 18):
            bg *= 2
        kernel = functools.partial(_quant_kernel, num_bits=num_bits,
                                   symmetric=symmetric, stochastic=stochastic)
        y = pl.pallas_call(
            kernel,
            grid=(groups // bg,),
            in_specs=[pl.BlockSpec((1, 1), lambda i: (0, 0),
                                   memory_space=pltpu.SMEM),
                      pl.BlockSpec((bg, row), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bg, row), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((groups, row), dtype),
        )(jnp.asarray(seed, jnp.int32).reshape(1, 1), xg)
        return y.reshape(shape)

    # CPU / fallback path: identical math, jax.random noise
    noise = None
    if stochastic:
        noise = jax.random.uniform(jax.random.PRNGKey(seed), (groups, row))
    return _quantize_rows(xg, num_bits, symmetric, stochastic,
                          noise).astype(dtype).reshape(shape)


class Quantizer:
    """API-parity shell of ops/quantizer/quantizer.py:32."""

    def __init__(self, q_int8=True):
        self.num_bits = 8 if q_int8 else 16

    def quantize(self, x, groups=1, symmetric=True, stochastic=False,
                 seed=None):
        return quantize(x, num_bits=self.num_bits, groups=groups,
                        symmetric=symmetric, stochastic=stochastic, seed=seed)
