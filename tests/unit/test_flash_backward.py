"""The resident flash backward in one pass: dq, dk and dv against the jnp
reference's gradients, and the kernel count that says which form ran.

The Pallas kernels run in interpret mode on the CPU at small sizes. Blocks
of 128 over 512 positions make four q blocks and four kv blocks, so dq
accumulates across kv blocks and dk/dv across q blocks inside the one
kernel; blocks of 64 are narrower than a lane tile and keep the two-call
form, which is checked too.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.ops.transformer.attention import mha_reference
from deepspeed_tpu.ops.transformer.flash import (flash_attention,
                                                 flash_attention_with_lse)


def _qkv(b, h, sq, sk, d, seed, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((b, h, s, d)), dtype)
            for s in (sq, sk, sk)]


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _grads(loss, q, k, v):
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _kernels(fn, *args):
    """Names of the pallas_calls in the jaxpr of ``jax.grad(fn)``."""
    loss = lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32))
    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(*args)
    return [e.params["name"] for e in jaxpr.jaxpr.eqns
            if e.primitive.name == "pallas_call"]


# (block, Sq, Sk, causal): one kernel over 4 x 4 blocks, causal and not,
# a q suffix of the keys (Sq < Sk, the decode offset), and blocks under a
# lane tile, which take the two-call form
CASES = {
    "causal": ("128", 512, 512, True),
    "noncausal": ("128", 512, 512, False),
    "q_suffix": ("128", 256, 512, True),
    "two_call_blocks_64": ("64", 256, 256, True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_backward_matches_reference(monkeypatch, case):
    block, sq, sk, causal = CASES[case]
    monkeypatch.setenv("DS_FLASH_BLOCK", block)
    q, k, v = _qkv(1, 2, sq, sk, 32, seed=sq + sk)
    w = jnp.asarray(np.random.default_rng(3).standard_normal(q.shape),
                    jnp.float32)

    def loss(attn):
        return lambda q, k, v: jnp.sum(w * attn(q, k, v))

    flash = lambda q, k, v: flash_attention(q, k, v, causal)
    assert _kernels(flash, q, k, v) == (
        ["flash_fwd", "flash_dq", "flash_dkv"] if block == "64"
        else ["flash_fwd", "flash_dkv"])
    got = _grads(loss(flash), q, k, v)
    ref = _grads(loss(lambda q, k, v: mha_reference(q, k, v, causal=causal)),
                 q, k, v)
    for g, r, name in zip(got, ref, ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   rtol=2e-4, err_msg=name)


def test_lse_cotangent_folds_into_the_one_pass(monkeypatch):
    """Ring attention differentiates through lse: its cotangent enters
    ``delta`` before the kernel, the same in the one-pass form."""
    monkeypatch.setenv("DS_FLASH_BLOCK", "128")
    B, H, S, D = 1, 2, 512, 32
    q, k, v = _qkv(B, H, S, S, D, seed=11)
    rng = np.random.default_rng(12)
    w = jnp.asarray(rng.standard_normal((B, H, S)), jnp.float32)

    def loss_flash(q, k, v):
        out, lse = flash_attention_with_lse(q, k, v, True, None)
        return jnp.sum(out ** 2) + jnp.sum(w * lse)

    def loss_ref(q, k, v):
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) * D ** -0.5
        logits = jnp.where(jnp.tril(jnp.ones((S, S), bool)), logits, -1e30)
        out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(logits, -1), v)
        return jnp.sum(out ** 2) + jnp.sum(w * jax.nn.logsumexp(logits, -1))

    assert _kernels(lambda q, k, v: flash_attention_with_lse(
        q, k, v, True, None)[0], q, k, v) == ["flash_fwd", "flash_dkv"]
    for g, r, name in zip(_grads(loss_flash, q, k, v),
                          _grads(loss_ref, q, k, v), ("dq", "dk", "dv")):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4,
                                   rtol=2e-4, err_msg=name)


def test_bf16_head_64_close_to_float32(monkeypatch):
    """The training dtype and head: bf16 operands, f32 accumulation."""
    monkeypatch.setenv("DS_FLASH_BLOCK", "128")
    q, k, v = _qkv(1, 2, 256, 256, 64, seed=5, dtype=jnp.bfloat16)

    def loss(attn):
        return lambda q, k, v: jnp.sum(attn(q, k, v).astype(jnp.float32) ** 2)

    got = _grads(loss(lambda q, k, v: flash_attention(q, k, v, True)),
                 q, k, v)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref = _grads(loss(lambda q, k, v: mha_reference(q, k, v, causal=True)),
                 *f32)
    for g, r, name in zip(got, ref, ("dq", "dk", "dv")):
        assert g.dtype == jnp.bfloat16
        assert _rel(g, r) < 2e-2, name


# (environment, head, expected pallas_calls of the gradient)
FORMS = {
    "resident_one_pass": ({}, 64, ["flash_fwd", "flash_dkv"]),
    "streaming": ({"DS_FLASH_STREAM": "1"}, 64,
                  ["flash_fwd", "flash_dq", "flash_dkv"]),
    "blocks_under_a_lane_tile": ({"DS_FLASH_BLOCK": "64"}, 64,
                                 ["flash_fwd", "flash_dq", "flash_dkv"]),
    "head_512": ({}, 512, ["flash_fwd", "flash_dq", "flash_dkv"]),
}


@pytest.mark.parametrize("form", list(FORMS))
def test_which_backward_runs(monkeypatch, form):
    env, d, names = FORMS[form]
    monkeypatch.delenv("DS_FLASH_STREAM", raising=False)
    monkeypatch.delenv("DS_FLASH_BLOCK", raising=False)
    for key, val in env.items():
        monkeypatch.setenv(key, val)
    x = jax.ShapeDtypeStruct((2, 4, 1024, d), jnp.bfloat16)
    assert _kernels(lambda q, k, v: flash_attention(q, k, v, True),
                    x, x, x) == names
