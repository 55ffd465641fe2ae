"""An expert layer for inference that holds a SHARE of the experts.

Expert parallelism gives each chip some of a layer's routed experts. This
is that chip's part, as one function the serving forward calls: the router
keeps its published width and routes every token over ALL experts
(:func:`route`: sigmoid scores, a selection bias used for the choice
alone, group-limited top-k, the chosen scores normalised over all of them
and scaled), and :func:`held_expert_mlp` computes what the experts held
HERE add, for the token-expert pairs that chose one of them. No token is
dropped and none is padded to a capacity: the pairs are sorted by expert
and each expert's rows go through its MLP as one group of a grouped
matrix product (:func:`grouped_matmul`; sharded_moe's
``_grouped_expert_mlp`` is the training form of the same idea). An expert
is of one of two kinds, which the model's configuration names
(:data:`EXPERT_KINDS`): SwiGLU (``silu``: gate and up side by side, two
products) or squared ReLU (``relu2``: ``relu(h U)^2 D``, no gate; ``U`` held
as the published ``up_proj`` is, ``[width, hidden]`` an expert, so that its
minor dimension fills whole 128-lane tiles at a width that does not). What the
absent experts would add is left out: across chips an exchange would
bring it, on one chip the layer runs without its exchange and nothing
stands in for the other chips.

:func:`held_expert_mlp` returns what it counted (pairs held, pairs absent,
the most pairs any one held expert got, the held experts with a pair), as
an int32 ``[4]`` on the device: the server lands it with the step's tokens
(``serving_moe_*`` counters).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _gmm

from deepspeed_tpu.ops._platform import interpret as _interpret

# the kinds of expert MLP: the first product's weight and whether it is
# held transposed (``[held, width, hidden]``); the second is ``down``
EXPERT_KINDS = {"silu": ("gate_up", False), "relu2": ("up", True)}

_HI = jax.lax.Precision.HIGHEST
# rows of one tile of the grouped product: an expert's group costs whole
# tiles, so small ones (a decode step gives a held expert a handful of
# rows); the other two tile whole weight columns
_TILE_M, _TILE_K, _TILE_N = 128, 1024, 1024


def group_limited_topk(choice, n_group, topk_group, k):
    """Indices ``[N, k]`` of the ``k`` largest of ``choice [N, X]`` among
    the ``topk_group`` groups (of ``n_group`` equal ones) whose two
    largest entries sum highest."""
    N, X = choice.shape
    grouped = choice.reshape(N, n_group, X // n_group)
    group_score = jax.lax.top_k(grouped, 2)[0].sum(-1)           # [N, G]
    kept = jax.lax.top_k(group_score, topk_group)[1]             # [N, g]
    keep = (kept[:, :, None] == jnp.arange(n_group)).any(1)      # [N, G]
    masked = jnp.where(keep[:, :, None], grouped, -jnp.inf)
    return jax.lax.top_k(masked.reshape(N, X), k)[1]


def route(h, router, router_bias, *, k, n_group, topk_group, scale):
    """The router, float32 throughout: ``h [N, E]`` -> the chosen experts
    ``[N, k]`` (ids over the router's whole width) and their weights
    ``[N, k]``. ``sigmoid(h @ router)`` are the scores; the bias is added
    for the CHOICE only; the weights are the chosen scores over their sum
    (all ``k``, held here or not), times ``scale``."""
    scores = jax.nn.sigmoid(jnp.dot(h.astype(jnp.float32),
                                    router.astype(jnp.float32),
                                    precision=_HI))
    chosen = group_limited_topk(scores + router_bias.astype(jnp.float32),
                                n_group, topk_group, k)
    picked = jnp.take_along_axis(scores, chosen, axis=1)
    return chosen, picked / (picked.sum(-1, keepdims=True) + 1e-20) * scale


@functools.cache
def _named_gmm(name):
    """megablox's grouped matmul under a jit named ``name``: the operation
    on the device trace's ``XLA Ops`` line takes the name of the innermost
    jit around the kernel, so megablox's own jit (named ``gmm``) is left
    out and the products carry the name their caller gives them."""
    def call(*args, **kwargs):
        return _gmm.__wrapped__(*args, **kwargs)

    call.__name__ = call.__qualname__ = name
    return jax.jit(call, static_argnames=("preferred_element_type",
                                          "tiling", "transpose_rhs",
                                          "interpret"))


def _tile(dim: int, most: int) -> int:
    """A tile of ``dim``: the largest multiple of 128 up to ``most`` that
    divides it, else the whole of a dimension up to twice ``most``, else
    ``most`` (the kernel masks the last, part-filled tile)."""
    for t in range(min(most, dim) // 128 * 128, 0, -128):
        if dim % t == 0:
            return t
    return dim if dim <= 2 * most else most


def grouped_matmul(rows, weights, group_sizes, out_dtype, name="gmm",
                   transposed=False):
    """``rows[group g's rows] @ weights[g]`` for rows sorted by group:
    ``rows [M, K]``, ``weights [G, K, N]`` (``[G, N, K]`` where
    ``transposed``), ``group_sizes [G]`` int32 -> ``[M, N]``. Rows past
    the groups' total come back UNDEFINED (the kernel never visits their
    tiles): the caller masks them. The Pallas
    grouped matmul that ships with jax (megablox ``gmm``) at tiles of
    ``_TILE_M`` rows: it visits a tile once for each group with rows in
    it and reads that expert's weights once. ``name``: what its operation
    is called on the device trace (megablox's own by default)."""
    M, K = rows.shape
    N = weights.shape[1 if transposed else 2]
    tm = min(_TILE_M, -(-M // 8) * 8)
    pad = -M % tm
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
    out = _named_gmm(name)(
        rows, weights, group_sizes, preferred_element_type=out_dtype,
        tiling=(tm, _tile(K, _TILE_K), _tile(N, _TILE_N)),
        transpose_rhs=transposed, interpret=_interpret())
    return out[:M] if pad else out


def held_expert_mlp(h, chosen, weights, experts, first, real, kind="silu",
                    name="gmm"):
    """What the experts held here add: ``sum_i w_i * expert_i(h)`` over a
    token's chosen experts ``i`` in ``[first, first + held)``.

    h ``[N, E]``; chosen/weights ``[N, k]`` (:func:`route`); experts of
    ``kind`` (:data:`EXPERT_KINDS`): ``gate_up [held, E, 2*M]`` (gate ‖
    up) for ``silu``, ``up [held, M, E]`` for ``relu2``, and ``down [held,
    M, E]``; real ``[N]`` bool: the rows that are tokens (a pad row is
    computed for no expert and counted nowhere); ``name``: what the
    grouped products are called on the device trace
    (:func:`grouped_matmul`). Returns ``[N, E]`` float32 and the counts
    ``[4]`` int32 (pairs held, pairs absent, most pairs of one held
    expert, held experts with a pair)."""
    if kind not in EXPERT_KINDS:
        raise ValueError(f"expert kind {kind!r}: one of "
                         f"{sorted(EXPERT_KINDS)}")
    N, k = chosen.shape
    held = experts["down"].shape[0]
    M = experts["down"].shape[1]
    local = (chosen - first).reshape(-1)
    here = (local >= 0) & (local < held) & jnp.repeat(real, k)
    key = jnp.where(here, local, held)              # absent pairs sort last
    order = jnp.argsort(key, stable=True)           # [N*k] pair ids
    group_sizes = jnp.zeros((held + 1,), jnp.int32).at[key].add(1)[:held]
    n_here = group_sizes.sum()
    x = h[order // k]                               # each pair's token row
    up, transposed = EXPERT_KINDS[kind]
    hidden = grouped_matmul(x, experts[up], group_sizes, h.dtype, name,
                            transposed)
    act = (jax.nn.silu(hidden[:, :M]) * hidden[:, M:] if kind == "silu"
           else jnp.square(jax.nn.relu(hidden)))
    y = grouped_matmul(act, experts["down"], group_sizes, jnp.float32, name)
    y = jnp.where((jnp.arange(N * k) < n_here)[:, None],
                  y * weights.reshape(-1)[order][:, None], 0.0)
    # back to the pairs' own order: a token's k rows lie together
    out = y[jnp.argsort(order)].reshape(N, k, -1).sum(1)
    counts = jnp.stack([n_here, real.sum() * k - n_here,
                        group_sizes.max(), (group_sizes > 0).sum()])
    return out, counts.astype(jnp.int32)
