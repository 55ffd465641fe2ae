"""A prefill chunk through the state-space recurrence in its dual form, and
the packed layout a slot's state has in the pool (package docstring)."""

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
LANES = 128


def packed_rows(heads: int, head_dim: int) -> int:
    """Rows of 128 lanes that hold a layer's ``heads x head_dim``
    channels."""
    return -(-heads * head_dim // LANES)


def to_heads(state, heads: int, head_dim: int):
    """A packed state ``[..., R, N, 128]`` as ``[..., H, P, N]``."""
    *lead, R, N, L = state.shape
    s = jnp.swapaxes(state, -1, -2).reshape(*lead, R * L, N)
    return s[..., :heads * head_dim, :].reshape(*lead, heads, head_dim, N)


def from_heads(s):
    """``[..., H, P, N]`` packed as ``[..., R, N, 128]``, pad channels 0."""
    *lead, H, P, N = s.shape
    R = packed_rows(H, P)
    s = s.reshape(*lead, H * P, N)
    s = jnp.pad(s, [(0, 0)] * len(lead) + [(0, R * LANES - H * P), (0, 0)])
    return jnp.swapaxes(s.reshape(*lead, R, LANES, N), -1, -2)


@jax.named_scope("ssm_chunk")
def chunk_scan(x, b, c, dt, a, s0):
    """``T`` tokens of one slot from the state ``s0`` before them: the
    outputs and the state after the last.

    x ``[T, H, P]``, b and c ``[T, G, N]`` (``G`` groups: head ``h`` reads
    group ``h // (H / G)``), dt ``[T, H]`` (the step ``dt`` after its
    softplus; 0 on a pad row, whose ``x`` is 0 too, so a pad row neither
    decays the state nor adds to it), a ``[H]`` (``A``, negative), s0
    ``[H, P, N]``; float32 throughout, every product at HIGHEST. Returns
    ``y [T, H, P]`` (no ``D`` skip) and ``S_T``.

    Within the chunk the dual form: with ``c_t = sum_{r<=t} dt_r A``, the
    weight of token ``s`` in output ``t >= s`` is ``exp(c_t - c_s) (C_t .
    B_s) dt_s``; the state before the chunk reaches output ``t`` decayed
    by ``exp(c_t)``, and token ``s`` reaches ``S_T`` by ``exp(c_{T-1} -
    c_s) dt_s``. The outputs take the heads as ``[G, H / G]``; the state
    after the chunk takes each head's group repeated (in the grouped form
    the compiler kept a second whole state pool beside the lone-slot
    prefill program at granite-4.0-h-micro's widths:
    tests/unit/test_serving_pool_layout.py)."""
    T, H, P = x.shape
    G = b.shape[1]
    xg = x.reshape(T, G, H // G, P)
    log_decay = dt * a                                  # [T, H], <= 0
    cum = jnp.cumsum(log_decay, axis=0)
    causal = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]
    gap = jnp.where(causal[None], (cum.T[:, :, None] - cum.T[:, None, :]),
                    -jnp.inf)                           # [H, t, s]
    scores = jnp.einsum("tgn,sgn->gts", c, b, precision=_HI)
    weights = (jnp.exp(gap) * dt.T[:, None, :]).reshape(G, H // G, T, T) \
        * scores[:, None]
    y = jnp.einsum("ghts,sghp->tghp", weights, xg, precision=_HI)
    y = y + jnp.exp(cum).reshape(T, G, H // G)[..., None] * jnp.einsum(
        "tgn,ghpn->tghp", c, s0.reshape(G, H // G, P, -1), precision=_HI)
    to_end = jnp.exp(cum[-1][None] - cum) * dt          # [T, H]
    s_end = jnp.exp(cum[-1])[:, None, None] * s0 + jnp.einsum(
        "sh,shp,shn->hpn", to_end, x, jnp.repeat(b, H // G, axis=1),
        precision=_HI)
    return y.reshape(T, H, P), s_end
