"""The decode walk's Pallas kernel against the jnp walk it replaces on
the TPU (serving/paged_attention.py), in interpret mode: bfloat16 pools
and queries as the chip holds them, the comparison at float32 rounding.

The batches are chosen for the kernel's own control flow: a slot walks
its own ``ceil(len / 16)`` blocks in groups of ``_GROUP``, and a slot
that holds nothing walks none.

The copies follow one stream of groups over all slots, ``_AHEAD`` groups
fetched or in flight beside the one reduced in a ring of ``_AHEAD + 1``
buffers (PR 36): the ``STREAMS`` batches are chosen for what that
schedule can get wrong, at both row forms.

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


RING = 6        # the deepest ring tried below
# the stream against the ring: lengths in BLOCKS, up to RING + 1 groups
STREAMS = {
    # more empty slots in a row than the ring is deep, first and last:
    # the cursor skips them at the start, and past the last there is
    # nothing to fetch
    "empty-runs-longer-than-the-ring": (
        [0] * (RING + 1) + [G + 1, 1, 2 * G] + [0] * (RING + 1)),
    # a slot with more groups than the ring is deep, then one-block
    # slots: the ring wraps inside the slot and then spans several slots
    "long-slot-then-one-block-slots": (
        [(RING + 1) * G] + [1] * (RING + 2)),
    # every slot one block: every look-ahead crosses RING - 1 slots
    "every-slot-one-block": [1] * (2 * RING + 1),
    # the last slot's last group is the stream's end, the ring still full
    "stream-ends-in-a-long-slot": [1, 0, G, (RING + 1) * G - 3],
    # a stream shorter than the ring: the start fetches all there is
    "stream-shorter-than-the-ring": [0, 2, 0, 0],
}
FORMS = {"heads-w1024-h16": (1024, 16), "heads-w1664-h25": (1600, 25),
         "latent-w256-h8": (160, 8)}
# the module's ring at every form; a ring of two and one of ``RING``
# (the turn's switch then has other arms) at one
RINGS = [(form, pa._AHEAD) for form in FORMS] + [
    ("heads-w1024-h16", 1), ("latent-w256-h8", 5)]


@pytest.mark.parametrize("stream", list(STREAMS))
@pytest.mark.parametrize("form,ahead", RINGS,
                         ids=[f"{f}-ahead{a}" for f, a in RINGS])
def test_the_stream_of_groups_across_slots(form, ahead, stream):
    """Both row forms through the ring: one stream of groups, whatever
    slot they belong to."""
    E, H = FORMS[form]
    latent = form.startswith("latent")
    W = -(-E // 128) * 128
    blocks = STREAMS[stream]
    B, MB = len(blocks), max(blocks) + 1
    n = 1 + sum(blocks)                         # blocks a layer
    rng = np.random.default_rng(len(stream) + E)
    # a last block is part full, but for the first slot's
    lens = [max(0, nb * BS - (3 * b) % BS) for b, nb in enumerate(blocks)]

    def pool():     # two layers' rows, the pad lanes zero as written
        rows = rng.standard_normal((2 * n, BS, E))
        return jnp.asarray(np.pad(rows, ((0, 0), (0, 0), (0, W - E))),
                           jnp.bfloat16)

    bt = np.zeros((B, MB), np.int32)
    free = iter(rng.permutation(np.arange(1, n)))
    for b, nb in enumerate(blocks):
        for i in range(nb):
            bt[b, i] = next(free)
    tables = (jnp.asarray(bt), jnp.asarray(lens, jnp.int32))
    if latent:
        V, scale = 128, E ** -0.5
        q = jnp.asarray(rng.standard_normal((B, H, E)), jnp.bfloat16)
        row = jnp.asarray(rng.standard_normal((B, E)), jnp.bfloat16)
        args = (q, row, None, n, pool(), None, *tables)
        want = pa.paged_chunk_attention(
            q[:, :, None], row[:, None], None, *args[3:], sm_scale=scale,
            v_width=V)[:, :, 0]
        got = pa._decode_kernel_call(*args, scale, ahead=ahead,
                                     interpret=True, v_width=V)
        assert got.shape == want.shape == (B, H, V)
    else:
        D = E // H
        q, k_cur, v_cur = (jnp.asarray(rng.standard_normal((B, H, D)),
                                       jnp.bfloat16) for _ in range(3))
        args = (q, k_cur, v_cur, n, pool(), pool(), *tables)
        want = _walk_one_token(*args)
        got = pa._decode_kernel_call(*args, D ** -0.5, ahead=ahead,
                                     interpret=True)
        assert got.shape == want.shape == (B, H, D)
    assert got.dtype == jnp.float32
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
