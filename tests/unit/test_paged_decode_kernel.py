"""The decode walk's Pallas kernel against the jnp loop it replaces on
the TPU (serving/paged_attention.py), in interpret mode: bfloat16 pools
and queries as the chip holds them, the comparison at float32 rounding.

The batches are chosen for the kernel's own control flow: a slot walks
its own ``ceil(len / 16)`` blocks in groups of ``_GROUP``, and the slot
before it starts its first group unless that one walked nothing."""

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
    want = pa._decode_loop(*args, None, None, D ** -0.5)
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
        np.asarray(pa._decode_loop(*args, None, None, D ** -0.5)),
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
