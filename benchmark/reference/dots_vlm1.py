"""Plain language model of ``dots.vlm1.inst`` (rednote-hilab; the public
``config.json``): a DeepSeek-V3-shaped decoder. RMSNorm; multi-head latent
attention (low-rank queries, one ``kv_lora_rank`` latent a token from which
every head's keys and values are EXPANDED, one rotary key shared by the
heads, YaRN frequencies); SwiGLU; leading dense layers, then expert layers
with a sigmoid router, a selection bias, group-limited top-k, one shared
expert.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: the expanded attention (no absorbed products), a
loop over the experts with a mask (no grouped product), no cache, no
batching tricks. It imports nothing of the program under test and takes
nothing the program made: the weights come from :func:`make_weights`
(seeded), which is also what the benchmark hands to the program.

It works one row and one layer at a time, 512 tokens of the row at a time (a
row is one request's prompt and served tokens, padded to a multiple of 512;
each block's queries attend over the whole row's keys under the causal
mask), each layer's weights made anew from the seed, so that it fits beside
nothing and so that its programs' shapes do not follow a row's length: only
the attention's does, which holds no weights and compiles in seconds. (A
layer as ONE program a row length, its experts unrolled, compiled for 7-8
minutes a run whenever the machine's compile cache had dropped it: PERF.md,
PR 35.)

It is given the SHARE of the model that the configuration file states
(``deployment``): the routed experts ``experts_held`` of each layer (the
router keeps its published width and routes over all of them; what the
absent experts would add is left out, and the partial sum is what goes
on), a slice of the vocabulary, and the file's depth.

Departures from the published description, each stated in the
configuration file:

* ``kv_b`` is kept as its two halves, ``kv_b_k`` (per head the ``nope`` key
  columns) and ``kv_b_v`` (the value columns), and an expert's ``gate`` and
  ``up`` matrices lie side by side in ``gate_up``: the program's layout of
  the same matrices, so that :func:`make_weights` can hand them over;
* a group that the router drops is masked with ``-inf`` where the published
  code writes 0.0 (the same choice whenever eight kept candidates score
  above 0, which sigmoid scores with a bias of N(0, 0.02) always do);
* rotary pairs are ``(2i, 2i+1)`` in place, where the published code first
  moves the even lanes ahead of the odd ones: the same scores, because the
  rotated halves meet only each other;
* norm gains are drawn N(1, 0.02) and the router's selection bias
  N(0, 0.02), so that a dropped gain or bias shows; matrices N(0, 0.02)
  (the public config gives no ``initializer_range``);
* no multi-token-prediction module and no vision tower (not served).

``quant=True`` is the CONTROL, not a mode of the reference: the operands of
every matrix product that the configuration states in bfloat16 are rounded
to a scaled 8-bit float (e4m3: 3 mantissa bits, per-tensor scale to the
format's maximum 448) — the nearest precision below bfloat16 that a later
PR would be tempted by. The router's product stays float32 there too, as
the configuration states it.
"""

import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0
_STD = 0.02
BLOCK = 512             # tokens that go through a layer's programs at once;
                        # a row is padded to a multiple of it


class Sizes(NamedTuple):
    L: int              # layers as run
    dense: int          # leading dense layers
    E: int              # hidden
    I: int              # dense MLP width
    M: int              # expert width
    X: int              # router outputs (published experts)
    held: Tuple[int, int]   # [first, stop) of them held here
    shared: int
    k: int              # experts a token
    n_group: int
    topk_group: int
    route_scale: float
    H: int
    q_lora: int
    kv_lora: int
    nope: int
    rope: int
    v: int
    V: int              # vocabulary rows held
    P: int
    eps: float
    theta: float
    yarn: Tuple[float, int, float, float, float, float]
    # factor, original positions, beta_fast, beta_slow, mscale, mscale_all_dim


def sizes(config) -> Sizes:
    rs = config["rope_scaling"]
    held = tuple(int(e) for e in config["deployment"]["experts_held"])
    assert held[1] - held[0] == config["n_routed_experts"], held
    return Sizes(
        L=int(config["num_hidden_layers"]),
        dense=int(config["first_k_dense_replace"]),
        E=int(config["hidden_size"]), I=int(config["intermediate_size"]),
        M=int(config["moe_intermediate_size"]),
        X=int(config["published"]["n_routed_experts"]), held=held,
        shared=int(config["n_shared_experts"]),
        k=int(config["num_experts_per_tok"]), n_group=int(config["n_group"]),
        topk_group=int(config["topk_group"]),
        route_scale=float(config["routed_scaling_factor"]),
        H=int(config["num_attention_heads"]),
        q_lora=int(config["q_lora_rank"]), kv_lora=int(config["kv_lora_rank"]),
        nope=int(config["qk_nope_head_dim"]),
        rope=int(config["qk_rope_head_dim"]), v=int(config["v_head_dim"]),
        V=int(config["vocab_size"]), P=int(config["n_positions"]),
        eps=float(config["rms_norm_eps"]), theta=float(config["rope_theta"]),
        yarn=(float(rs["factor"]),
              int(rs["original_max_position_embeddings"]),
              float(rs["beta_fast"]), float(rs["beta_slow"]),
              float(rs["mscale"]), float(rs["mscale_all_dim"])))


# ------------------------------------------------------------------ weights
def seed_words(seed: int) -> np.ndarray:
    """``--seed`` may exceed 32 signed bits: carry it as two uint32 words,
    traced, so that a new seed never compiles a new program."""
    seed = int(seed)
    return np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF], np.uint32)


def _key(words, *path):
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(0),
                                                words[0]), words[1])
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def _normal(key, shape, mean=0.0):
    return mean + _STD * jax.random.normal(key, shape, jnp.float32)


def layer_weights(words, layer, sz: Sizes, moe: bool):
    """One layer's weights, float32, in the program's tree layout."""
    k = iter(jax.random.split(_key(words, 1, layer), 24))
    E, H, M = sz.E, sz.H, sz.M
    n_held = sz.held[1] - sz.held[0]

    def mlp(width):
        return {"gate": _normal(next(k), (E, width)),
                "up": _normal(next(k), (E, width)),
                "down": _normal(next(k), (width, E))}

    p = {"norm_1": _normal(next(k), (E,), 1.0),
         "norm_2": _normal(next(k), (E,), 1.0),
         "attn": {"q_a": _normal(next(k), (E, sz.q_lora)),
                  "q_norm": _normal(next(k), (sz.q_lora,), 1.0),
                  "q_b": _normal(next(k), (sz.q_lora, H * (sz.nope + sz.rope))),
                  "kv_a": _normal(next(k), (E, sz.kv_lora + sz.rope)),
                  "kv_norm": _normal(next(k), (sz.kv_lora,), 1.0),
                  "kv_b_k": _normal(next(k), (sz.kv_lora, H, sz.nope)),
                  "kv_b_v": _normal(next(k), (sz.kv_lora, H, sz.v)),
                  "o": _normal(next(k), (H * sz.v, E))}}
    if moe:
        p["moe"] = {"router": _normal(next(k), (E, sz.X)),
                    "router_bias": _normal(next(k), (sz.X,)),
                    "shared": mlp(sz.shared * M),
                    "experts": {
                        "gate_up": _normal(next(k), (n_held, E, 2 * M)),
                        "down": _normal(next(k), (n_held, M, E))}}
    else:
        p["mlp"] = mlp(sz.I)
    return p


def outer_weights(words, sz: Sizes):
    k = jax.random.split(_key(words, 0), 3)
    return {"embed": _normal(k[0], (sz.V, sz.E)),
            "head": _normal(k[1], (sz.V, sz.E)),
            "norm_f": _normal(k[2], (sz.E,), 1.0)}


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, static_argnames=("sz", "moe", "dtype"))
def _layer_in(words, layer, sz, moe, dtype):
    return _cast(layer_weights(words, layer, sz, moe), dtype)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _outer_in(words, sz, dtype):
    return _cast(outer_weights(words, sz), dtype)


def make_weights(words, sz: Sizes, dtype):
    """The whole share in the program's layout (the tree of
    ``deepspeed_tpu.models.mla_moe``), made on the device in ``dtype``, a
    layer a call: a layer of experts is a fifth of the chip."""
    tree = _outer_in(words, sz, dtype)
    for i in range(sz.L):
        tree[f"h_{i}"] = _layer_in(words, np.int32(i), sz, i >= sz.dense,
                                   dtype)
    return tree


def layer_as_served(words, layer, sz, dtype):
    """Drawn in float32, rounded to the configuration's precision, and back:
    the values the program holds."""
    return _cast(_layer_in(words, np.int32(layer), sz, layer >= sz.dense,
                           dtype), jnp.float32)


# ------------------------------------------------------------------ control
def _f8(x):
    """Round to a scaled e4m3: per-tensor scale to 448, three mantissa bits
    (round to nearest even on the float32 bit pattern)."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * (_F8_MAX / amax)
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) \
        & jnp.uint32(0xFFF00000)
    return jnp.clip(jax.lax.bitcast_convert_type(bits, jnp.float32),
                    -_F8_MAX, _F8_MAX) * (amax / _F8_MAX)


def _mm(spec, a, b, quant):
    if quant:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ forward
def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def yarn_inv_freq(sz: Sizes) -> np.ndarray:
    """``f_i = theta^(-2i/d)``; ``cd(n) = d ln(P0 / (2 pi n)) / (2 ln
    theta)``; ``low = max(floor(cd(beta_fast)), 0)``, ``high =
    min(ceil(cd(beta_slow)), d - 1)``; ``ramp_i = clip((i - low) / (high -
    low), 0, 1)``; ``inv_freq_i = f_i (1 - ramp_i) + f_i / factor ramp_i``."""
    factor, original, fast, slow, _, _ = sz.yarn
    d = sz.rope
    f = sz.theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)

    def cd(n):
        return d * math.log(original / (2 * math.pi * n)) \
            / (2 * math.log(sz.theta))

    low, high = max(math.floor(cd(fast)), 0), min(math.ceil(cd(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0.0, 1.0)
    return (f * (1 - ramp) + f / factor * ramp).astype(np.float32)


def softmax_scale(sz: Sizes) -> float:
    """``(nope + rope)^-1/2 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``."""
    factor, _, _, _, _, all_dim = sz.yarn
    m = 0.1 * all_dim * math.log(factor) + 1.0
    return (sz.nope + sz.rope) ** -0.5 * m * m


def _rope_at(x, pos, sz: Sizes):
    """``x [T, ..., rope]`` at positions ``pos [T]``: the pair ``(2i, 2i+1)``
    turns by ``pos inv_freq_i``. (cos and sin carry the factor ``mscale /
    mscale_all_dim`` of YaRN's two ``m``; it is 1 here and asserted so.)"""
    _, _, _, _, mscale, all_dim = sz.yarn
    assert mscale == all_dim, "cos/sin factor other than 1: not written"
    angle = pos.astype(jnp.float32)[:, None] * yarn_inv_freq(sz)
    angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    pairs = x.reshape(x.shape[:-1] + (-1, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     -1).reshape(x.shape)


def _project(x, first, norm_1, a, sz: Sizes, quant):
    """What the attention needs of ``BLOCK`` tokens ``x [B, E]`` at positions
    ``first..`` (``a``: the layer's attention weights): per-head queries
    (nope, rotated pe), the rotated shared key, and the keys and values
    EXPANDED from the normed latent."""
    B = x.shape[0]
    pos = first + jnp.arange(B)
    h = _rms(x, norm_1, sz.eps)
    q = _mm("te,ef->tf", _rms(_mm("te,eq->tq", h, a["q_a"], quant),
                              a["q_norm"], sz.eps), a["q_b"], quant)
    q = q.reshape(B, sz.H, sz.nope + sz.rope)
    ckv = _mm("te,ec->tc", h, a["kv_a"], quant)
    c_kv = _rms(ckv[:, :sz.kv_lora], a["kv_norm"], sz.eps)
    return {"q_nope": q[..., :sz.nope],
            "q_pe": _rope_at(q[..., sz.nope:], pos, sz),
            "k_pe": _rope_at(ckv[:, sz.kv_lora:], pos, sz),
            "k_nope": _mm("tc,chd->thd", c_kv, a["kv_b_k"], quant),
            "v": _mm("tc,chd->thd", c_kv, a["kv_b_v"], quant)}


def _attend(q_nope, q_pe, first, k_nope, k_pe, v, sz: Sizes, quant):
    """Causal softmax attention of ``BLOCK`` queries at positions
    ``first..`` over the whole row's keys and values ``[T, ...]``."""
    scores = (_mm("shd,thd->hst", q_nope, k_nope, quant)
              + _mm("shd,td->hst", q_pe, k_pe, quant)) * softmax_scale(sz)
    seen = (jnp.arange(k_pe.shape[0])[None, :]
            <= first + jnp.arange(q_pe.shape[0])[:, None])
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return _mm("hst,thd->shd", probs, v, quant).reshape(q_pe.shape[0], -1)


def _swiglu(h, gate, up, down, quant):
    return _mm("tm,me->te", jax.nn.silu(_mm("te,em->tm", h, gate, quant))
               * _mm("te,em->tm", h, up, quant), down, quant)


def _route(h, p, sz: Sizes):
    """The chosen experts ``[T, k]`` and their weights: sigmoid scores; the
    bias joins them for the choice only; a group's score is the sum of its
    two best; the ``topk_group`` best groups stay; the ``k`` best inside
    them are chosen; their scores, normalised over all ``k`` (held here or
    not) and scaled, are the weights. float32, never quantised."""
    scores = jax.nn.sigmoid(jnp.einsum("te,ex->tx", h, p["router"],
                                       precision=_HI))
    choice = scores + p["router_bias"]
    T = h.shape[0]
    groups = choice.reshape(T, sz.n_group, -1)
    group_score = jax.lax.top_k(groups, 2)[0].sum(-1)
    kept = jax.lax.top_k(group_score, sz.topk_group)[1]
    keep = jnp.zeros((T, sz.n_group), bool).at[
        jnp.arange(T)[:, None], kept].set(True)
    inside = jnp.where(keep[:, :, None], groups, -jnp.inf).reshape(T, sz.X)
    chosen = jax.lax.top_k(inside, sz.k)[1]
    w = jnp.take_along_axis(scores, chosen, 1)
    return chosen, w / (w.sum(-1, keepdims=True) + 1e-20) * sz.route_scale


def _moe(h, p, sz: Sizes, quant):
    """The shared expert, and of the routed experts those held here, one
    after the other over every token, each weighted by the router's weight
    for it (0 where the token did not choose it)."""
    chosen, w = _route(h, p, sz)
    shared = _swiglu(h, p["shared"]["gate"], p["shared"]["up"],
                     p["shared"]["down"], quant)

    def one_expert(y, expert):
        gate_up, down, e = expert
        w_e = jnp.where(chosen == e, w, 0.0).sum(-1)
        return y + w_e[:, None] * _swiglu(h, gate_up[:, :sz.M],
                                          gate_up[:, sz.M:], down,
                                          quant), None

    return jax.lax.scan(one_expert, shared, (
        p["experts"]["gate_up"], p["experts"]["down"],
        jnp.arange(sz.held[0], sz.held[1])))[0]


def _finish(x, o, p, sz: Sizes, quant):
    """The rest of the layer for ``BLOCK`` tokens: the attention's output
    projection onto the residual, then the dense MLP or the experts."""
    x = x + _mm("tf,fe->te", o, p["attn"]["o"], quant)
    h = _rms(x, p["norm_2"], sz.eps)
    if "moe" in p:
        return x + _moe(h, p["moe"], sz, quant)
    return x + _swiglu(h, p["mlp"]["gate"], p["mlp"]["up"], p["mlp"]["down"],
                       quant)


_project_fwd = jax.jit(_project, static_argnames=("sz", "quant"))
_attend_fwd = jax.jit(_attend, static_argnames=("sz", "quant"))
_finish_fwd = jax.jit(_finish, static_argnames=("sz", "quant"))


def _layer(x, p, sz: Sizes, quant):
    """One layer over one row ``x [T, E]``, ``BLOCK`` tokens at a time:
    three small programs whose shapes do not follow the row's length (the
    attention's does, and it holds no weights), so that a new length
    compiles next to nothing."""
    starts = range(0, x.shape[0], BLOCK)
    parts = [_project_fwd(x[s:s + BLOCK], np.int32(s), p["norm_1"], p["attn"],
                          sz=sz, quant=quant) for s in starts]
    k_nope, k_pe, v = (jnp.concatenate([part[name] for part in parts])
                       for name in ("k_nope", "k_pe", "v"))
    return jnp.concatenate([
        _finish_fwd(x[s:s + BLOCK], _attend_fwd(
            part["q_nope"], part["q_pe"], np.int32(s), k_nope, k_pe, v,
            sz=sz, quant=quant), p, sz=sz, quant=quant)
        for s, part in zip(starts, parts)])


def _hidden_rows(config, seed, ids, quant):
    """The last layer's output of every row of ``ids`` (one array a row),
    with the outer weights as served: a row at a time, the layers
    outermost, each layer's weights made once from the seed."""
    sz = sizes(config)
    dtype = jnp.dtype(config["precision"])
    words = seed_words(seed)
    outer = _cast(_outer_in(words, sz, dtype), jnp.float32)
    xs = [outer["embed"][jnp.asarray(row)] for row in ids]
    for i in range(sz.L):
        p = layer_as_served(words, i, sz, dtype)
        xs = [_layer(x, p, sz, quant) for x in xs]
        del p
    return xs, outer, sz


def full_forward(config, seed, ids, quant=False):
    """Logits ``[n, T, V]`` float32 of rows ``ids [n, T]`` (numpy): the
    whole forward at once, for a test's small sizes."""
    T = ids.shape[1]
    ids = np.pad(ids, ((0, 0), (0, -T % BLOCK)))
    xs, outer, sz = _hidden_rows(config, seed, ids, quant)
    return np.stack([np.asarray(_mm(
        "te,ve->tv", _rms(x[:T], outer["norm_f"], sz.eps), outer["head"],
        quant)) for x in xs])


# ------------------------------------------------- serving: teacher forcing
@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _read_row(x, norm_f, head, picks, sz, quant):
    logits = _mm("te,ve->tv", _rms(x, norm_f, sz.eps), head, quant)
    picked = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
    return logits.max(-1), picked, jnp.argmax(logits, -1).astype(jnp.int32)


def teacher_forced(config, seed, ids, picks, quant=False):
    """One forward over ``ids`` [n, T] (right-padded; causal, so the pad
    changes nothing before it) on the weights as served (drawn in float32,
    rounded to the configuration's precision). For each position returns
    the best logit over the vocabulary held, the logit of ``picks[n, T]``
    and the arg-best token, as numpy arrays."""
    n, T = ids.shape
    wide = -(-T // BLOCK) * BLOCK
    ids = np.pad(ids, ((0, 0), (0, wide - T)))
    picks = np.pad(picks, ((0, 0), (0, wide - T)))
    xs, outer, sz = _hidden_rows(config, seed, ids, quant)
    outs = [[np.concatenate(part)[:T] for part in zip(*(
        [np.asarray(o) for o in _read_row(
            x[s:s + BLOCK], outer["norm_f"], outer["head"],
            jnp.asarray(row[s:s + BLOCK]), sz=sz, quant=quant)]
        for s in range(0, wide, BLOCK)))] for x, row in zip(xs, picks)]
    return tuple(np.stack([o[j] for o in outs]) for j in range(3))
