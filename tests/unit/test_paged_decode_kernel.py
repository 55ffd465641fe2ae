"""The decode walk's Pallas kernel against the jnp walk it replaces on
the TPU (serving/paged_attention.py), in interpret mode: bfloat16 pools
and queries as the chip holds them, the comparison at float32 rounding.

The batches are chosen for the kernel's own control flow: a slot walks
its own ``ceil(len / 16)`` blocks in groups of ``_GROUP``, and the slot
before it starts its first group unless that one walked nothing.

The jnp walk itself, the one loop behind decode, prefill and verify, is
held to a dense masked softmax over a contiguous view of the pool, at
each caller's shape."""

import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.serving import paged_attention as pa

BS, MB, N = 16, 20, 48          # block size, table width, blocks a layer
G = pa._GROUP
# eight slots each, so that a shape's cases share one traced kernel
BATCHES = {
    # every block boundary, a length over one group and one over two,
    # empty slots first, between and last
    "ragged": [0, 1, 15, 16, 17, G * BS + 3, 2 * G * BS + 5, 0],
    "empty-runs-between": [40, 0, 0, 33, 0, 0, 0, 5],
    "all-empty": [0] * 8,
    "first-slot-two-groups-exactly": [2 * G * BS] + [0] * 7,
    # lengths with tables that were never filled: every read is the null
    # block, whose rows both walks must weigh alike
    "null-tables": [20, 0, 150, 0, 0, 0, 0, 1],
}
SHAPES = {"w1024-h16": (1024, 16), "w1664-h25": (1600, 25)}


def _walk_one_token(q, k_cur, v_cur, *rest, **kw):
    """The jnp walk at ``C = 1``: what ``paged_decode_attention`` runs
    where the kernel does not."""
    return pa.paged_chunk_attention(
        q[:, :, None], k_cur[:, :, None], v_cur[:, :, None], *rest,
        **kw)[:, :, 0]


# the second of two layers' rows everywhere, the first once a shape
@pytest.mark.parametrize("batch,first_layer", [(b, 1) for b in BATCHES]
                         + [("ragged", 0)])
@pytest.mark.parametrize("shape", list(SHAPES))
def test_kernel_matches_the_jnp_loop(shape, batch, first_layer):
    E, H = SHAPES[shape]
    D, W = E // H, -(-E // 128) * 128
    lens = BATCHES[batch]
    B = len(lens)
    rng = np.random.default_rng(len(batch) + E)

    def pool():     # two layers' rows, the pad lanes zero as written
        rows = rng.standard_normal((2 * N, BS, E))
        return jnp.asarray(np.pad(rows, ((0, 0), (0, 0), (0, W - E))),
                           jnp.bfloat16)

    k_pool, v_pool = pool(), pool()
    bt = np.zeros((B, MB), np.int32)
    if batch != "null-tables":
        free = iter(rng.permutation(np.arange(1, N)))
        for b, n in enumerate(lens):
            for i in range(-(-n // BS)):
                bt[b, i] = next(free)
    q, k_cur, v_cur = (jnp.asarray(rng.standard_normal((B, H, D)),
                                   jnp.bfloat16) for _ in range(3))
    args = (q, k_cur, v_cur, first_layer * N, k_pool, v_pool,
            jnp.asarray(bt), jnp.asarray(lens, jnp.int32))
    want = _walk_one_token(*args)
    got = pa._decode_kernel_call(*args, D ** -0.5, interpret=True)
    assert got.shape == want.shape == (B, H, D) and got.dtype == jnp.float32
    # values are O(1); bfloat16 rounding of a probability would show as 4e-3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=4e-6)


def test_float32_queries_are_not_rounded():
    """A float32 query (an engine that computes in float32 over bfloat16
    pools) goes through the same three-term split as the probabilities."""
    E, H, D = 256, 4, 64
    rng = np.random.default_rng(0)
    pool = lambda: jnp.asarray(rng.standard_normal((N, BS, E)), jnp.bfloat16)
    bt = np.zeros((2, MB), np.int32)
    bt[0, :3], bt[1, :1] = [5, 9, 2], [7]
    q, k_cur, v_cur = (jnp.asarray(rng.standard_normal((2, H, D)),
                                   jnp.float32) for _ in range(3))
    args = (q, k_cur, v_cur, 0, pool(), pool(), jnp.asarray(bt),
            jnp.asarray([40, 9], jnp.int32))
    np.testing.assert_allclose(
        np.asarray(pa._decode_kernel_call(*args, D ** -0.5, interpret=True)),
        np.asarray(_walk_one_token(*args)),
        rtol=0, atol=4e-6)


def test_the_dispatch_is_the_jnp_loop_off_the_tpu(monkeypatch):
    """Platform, pool dtype and mesh size choose, nothing else: off the
    TPU, over int8 or float32 pools and under a multi-device mesh the
    kernel does not run."""
    from deepspeed_tpu.utils import groups
    groups.destroy()
    assert not pa.decode_kernel_runs(jnp.bfloat16)          # the CPU
    monkeypatch.setattr(pa, "_interpret", lambda: False)    # "a TPU"
    assert pa.decode_kernel_runs(jnp.bfloat16)
    assert not pa.decode_kernel_runs(jnp.int8)
    assert not pa.decode_kernel_runs(jnp.float32)
    groups.initialize()                 # the 8 virtual devices of conftest
    try:
        assert groups.get_mesh().size > 1
        assert not pa.decode_kernel_runs(jnp.bfloat16)
    finally:
        groups.destroy()


@pytest.mark.parametrize("kv", ["float32", "int8"])
@pytest.mark.parametrize("caller,B,C", [("decode", 3, 1), ("prefill", 1, 6),
                                        ("verify", 3, 4)])
def test_chunk_walk_matches_dense(caller, B, C, kv):
    """The one walk at its three callers' shapes, over float and int8
    pools with rows of 2.5 lanes (320 in 384): each slot's window is laid
    out contiguously HERE, from the pool and the slot's table, and a
    dense softmax runs over it and the chunk, masked to the tokens the
    slot holds and to the chunk's causal order."""
    E, H, W, L = 320, 5, 384, 2
    D = E // H
    lens = [37, 0, 16][:B]
    rng = np.random.default_rng(B * 10 + C)
    bt = np.zeros((B, MB), np.int32)
    free = iter(rng.permutation(np.arange(1, N)))
    for b, n in enumerate(lens):
        for i in range(-(-n // BS)):
            bt[b, i] = next(free)

    def pool():     # (pool, scale pool or None, its float32 values)
        rows = rng.standard_normal((L * N, BS, H, D)).astype(np.float32)
        scale = None
        if kv == "int8":
            scale = np.abs(rows).max(-1) / 127.0                # [.., H]
            rows = np.round(rows / scale[..., None]).astype(np.int8)
        stored = np.pad(rows.reshape(L * N, BS, E),
                        ((0, 0), (0, 0), (0, W - E)))
        values = rows.astype(np.float32)
        if scale is not None:
            values = values * scale[..., None]
            scale = jnp.asarray(np.pad(scale, ((0, 0), (0, 0),
                                               (0, 128 - H))))
        return jnp.asarray(stored), scale, values

    (k_pool, k_scale, k_val), (v_pool, v_scale, v_val) = pool(), pool()
    q, k_new, v_new = (rng.standard_normal((B, H, C, D)).astype(np.float32)
                       for _ in range(3))
    layer = 1
    got = pa.paged_chunk_attention(
        jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new), layer * N,
        k_pool, v_pool, jnp.asarray(bt), jnp.asarray(lens, jnp.int32),
        k_scale_pool=k_scale, v_scale_pool=v_scale)
    assert got.shape == (B, H, C, D) and got.dtype == jnp.float32

    T = MB * BS
    for b, n in enumerate(lens):
        def window(values, new):    # [H, T + C, D]: the past, the chunk
            past = values[layer * N + bt[b]].reshape(T, H, D)
            return np.concatenate([past.transpose(1, 0, 2), new[b]], axis=1)
        keys, vals = window(k_val, k_new), window(v_val, v_new)
        seen = np.concatenate(
            [np.broadcast_to(np.arange(T) < n, (C, T)),
             np.tril(np.ones((C, C), bool))], axis=1)           # [C, T + C]
        s = np.einsum("hcd,htd->hct", q[b], keys) * D ** -0.5
        s = np.where(seen, s, -np.inf)
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hct,htd->hcd", p / p.sum(-1, keepdims=True), vals)
        np.testing.assert_allclose(np.asarray(got[b]), want, rtol=0,
                                   atol=2e-6, err_msg=f"{caller} slot {b}")
