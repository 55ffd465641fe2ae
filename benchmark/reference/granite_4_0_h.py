"""Plain language model of ``granite-4.0-h-micro`` (ibm-granite; the public
``config.json``, ``model_type`` ``granitemoehybrid`` with no experts): a
decoder whose layers are Mamba-2 state-space mixers or grouped-query
attention without positions, as ``layer_types`` says, each followed by a
SwiGLU MLP; RMSNorm; every mixer and MLP output scaled by
``residual_multiplier``, the embedding by ``embedding_multiplier``, the
tied head's logits divided by ``logits_scaling``; attention scores scaled
by ``attention_multiplier``.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. The state-space mixer is computed in its DUAL
(quadratic) form and never forms the state:

    y_t[n] = sum_{s<=t} exp(sum_{r=s+1..t} dt_r[n] A[n]) (C_t . B_s) dt_s[n] x_s[n]
             + D[n] x_t[n]

with the exponent a masked segment sum over the row (not a difference of
cumulative sums), over the whole row at once: independent of the
program's recurrence and of its chunked scan. It imports nothing of the
program under test and takes nothing the program made: the weights come
from :func:`make_weights` (seeded), which is also what the benchmark
hands to the program.

It works a layer at a time over all rows: what holds weights (the
projections, the MLP, the head) over the rows' tokens laid end to end,
``BLOCK`` tokens a program, so that those programs' shapes do not follow a
row's length; the convolution, the state-space mixer and the attention a
row at a time over the whole row (no weights: a new length compiles in
seconds). Each layer's weights are made once from the seed. A row's pad
(right of its tokens) changes nothing before it: every mixer is causal.

Departures from the published description, each stated in the
configuration file: the convolution's weight is held ``[d_conv,
channels]`` (tap ``k`` meets the input ``d_conv - 1 - k`` tokens back; the
published ``[channels, 1, d_conv]``) and an MLP's gate and up matrices lie
side by side in ``w_in``: the program's layout of the same matrices; the
weights are drawn as ``assumed`` says.

``quant=True`` is the CONTROL, not a mode of the reference: the operands of
every matrix product that the configuration states in bfloat16 (the
projections, the MLP, attention's two products, the head) are rounded to a
scaled 8-bit float (e4m3: 3 mantissa bits, per-tensor scale to the
format's maximum 448) — the nearest precision below bfloat16. The state's
arithmetic stays float32 there too, as the configuration states it.
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
BLOCK = 512             # tokens that go through a weight program at once


class Sizes(NamedTuple):
    attention: Tuple[bool, ...]     # a layer is attention (else Mamba-2)
    E: int
    I: int
    H: int              # query heads
    K: int              # key/value heads
    D: int              # head size
    Hm: int             # Mamba heads
    P: int              # Mamba head size
    N: int              # d_state
    taps: int           # d_conv
    V: int
    eps: float
    emb: float
    att: float
    res: float
    logits: float

    @property
    def L(self) -> int:
        return len(self.attention)

    @property
    def inner(self) -> int:
        return self.Hm * self.P

    @property
    def channels(self) -> int:
        return self.inner + 2 * self.N


def sizes(config) -> Sizes:
    assert config["mamba_n_groups"] == 1, "grouped B and C: not written"
    return Sizes(
        attention=tuple(t == "attention" for t in config["layer_types"]),
        E=int(config["hidden_size"]), I=int(config["intermediate_size"]),
        H=int(config["num_attention_heads"]),
        K=int(config["num_key_value_heads"]),
        D=int(config["hidden_size"]) // int(config["num_attention_heads"]),
        Hm=int(config["mamba_n_heads"]), P=int(config["mamba_d_head"]),
        N=int(config["mamba_d_state"]), taps=int(config["mamba_d_conv"]),
        V=int(config["vocab_size"]), eps=float(config["rms_norm_eps"]),
        emb=float(config["embedding_multiplier"]),
        att=float(config["attention_multiplier"]),
        res=float(config["residual_multiplier"]),
        logits=float(config["logits_scaling"]))


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


def _uniform(key, shape, lo, hi):
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def layer_weights(words, layer, sz: Sizes, attention: bool):
    """One layer's weights, float32, in the program's tree layout:
    matrices N(0, 0.02), norm gains N(1, 0.02), and Mamba-2's published
    dynamics: ``A_log = log U[1, 16]``, ``dt_bias`` the inverse softplus
    of a step log-uniform on [0.001, 0.1], ``D = 1``, the convolution's
    weight and bias U(-1/2, 1/2)."""
    k = iter(jax.random.split(_key(words, 1, layer), 16))
    E, I = sz.E, sz.I
    p = {"norm_1": _normal(next(k), (E,), 1.0),
         "norm_2": _normal(next(k), (E,), 1.0),
         "mlp": {"w_in": _normal(next(k), (E, 2 * I)),
                 "w_out": _normal(next(k), (I, E))}}
    if attention:
        p["attn"] = {"q": _normal(next(k), (E, sz.H * sz.D)),
                     "k": _normal(next(k), (E, sz.K * sz.D)),
                     "v": _normal(next(k), (E, sz.K * sz.D)),
                     "o": _normal(next(k), (sz.H * sz.D, E))}
        return p
    step = jnp.exp(_uniform(next(k), (sz.Hm,), math.log(1e-3),
                            math.log(1e-1)))
    p["mamba"] = {
        "in_proj": _normal(next(k), (E, sz.inner + sz.channels + sz.Hm)),
        "conv_w": _uniform(next(k), (sz.taps, sz.channels), -0.5, 0.5),
        "conv_b": _uniform(next(k), (sz.channels,), -0.5, 0.5),
        "A_log": jnp.log(_uniform(next(k), (sz.Hm,), 1.0, 16.0)),
        "D": jnp.ones((sz.Hm,), jnp.float32),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "norm": _normal(next(k), (sz.inner,), 1.0),
        "out_proj": _normal(next(k), (sz.inner, E))}
    return p


def outer_weights(words, sz: Sizes):
    k = jax.random.split(_key(words, 0), 2)
    return {"embed": _normal(k[0], (sz.V, sz.E)),
            "norm_f": _normal(k[1], (sz.E,), 1.0)}


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, static_argnames=("sz", "attention", "dtype"))
def _layer_in(words, layer, sz, attention, dtype):
    return _cast(layer_weights(words, layer, sz, attention), dtype)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _outer_in(words, sz, dtype):
    return _cast(outer_weights(words, sz), dtype)


def make_weights(words, sz: Sizes, dtype):
    """The whole model in the program's layout (the tree of
    ``deepspeed_tpu.models.ssm_hybrid``), made on the device in
    ``dtype``, a layer a call."""
    tree = _outer_in(words, sz, dtype)
    for i, attention in enumerate(sz.attention):
        tree[f"h_{i}"] = _layer_in(words, np.int32(i), sz, attention, dtype)
    return tree


def layer_as_served(words, layer, sz, dtype):
    """Drawn in float32, rounded to the configuration's precision, and back:
    the values the program holds."""
    return _cast(_layer_in(words, np.int32(layer), sz, sz.attention[layer],
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


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _mix_in(x, p, sz, quant):
    """What a layer's mixer takes of ``BLOCK`` tokens: Mamba's ``in_proj``
    output, or attention's q, k and v side by side."""
    h = _rms(x, p["norm_1"], sz.eps)
    if "mamba" in p:
        return _mm("te,ef->tf", h, p["mamba"]["in_proj"], quant)
    a = p["attn"]
    return jnp.concatenate([_mm("te,ef->tf", h, a[n], quant)
                            for n in ("q", "k", "v")], axis=-1)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _mix_out(x, mixed, p, sz, quant):
    """The rest of the layer for ``BLOCK`` tokens: the mixer's output
    projection and the MLP, each scaled onto the residual."""
    w_out = p["mamba"]["out_proj"] if "mamba" in p else p["attn"]["o"]
    x = x + sz.res * _mm("tf,fe->te", mixed, w_out, quant)
    h = _rms(x, p["norm_2"], sz.eps)
    gu = _mm("te,ef->tf", h, p["mlp"]["w_in"], quant)
    return x + sz.res * _mm("tf,fe->te", jax.nn.silu(gu[:, :sz.I])
                            * gu[:, sz.I:], p["mlp"]["w_out"], quant)


def segment_sum(a):
    """``a [H, T]`` -> ``[H, T, T]`` with ``[h, t, s] = sum_{s<r<=t}
    a[h, r]`` for ``s <= t`` and ``-inf`` above the diagonal: ``a`` laid
    along ``s``, masked to ``r > s`` and summed down ``t``."""
    T = a.shape[-1]
    r = jnp.arange(T)
    along = jnp.where(r[:, None] > r[None, :], a[:, :, None], 0.0)
    summed = jnp.cumsum(along, axis=1)
    return jnp.where(r[:, None] >= r[None, :], summed, -jnp.inf)


@functools.partial(jax.jit, static_argnames=("sz",))
def _mamba_row(proj, m, sz):
    """A row's Mamba-2 mixer from its ``in_proj`` output ``[T, ...]``:
    causal depthwise convolution (zeros before the first token), SiLU,
    the state-space mixer in its dual form over the whole row, the gate and
    the gated RMSNorm."""
    T = proj.shape[0]
    inner, W, Hm, P, N = sz.inner, sz.channels, sz.Hm, sz.P, sz.N
    z, xbc, dt = proj[:, :inner], proj[:, inner:inner + W], \
        proj[:, inner + W:]
    padded = jnp.pad(xbc, ((sz.taps - 1, 0), (0, 0)))
    conv = m["conv_b"] + sum(padded[k:k + T] * m["conv_w"][k]
                             for k in range(sz.taps))
    conv = jax.nn.silu(conv)
    x = conv[:, :inner].reshape(T, Hm, P)
    b, c = conv[:, inner:inner + N], conv[:, inner + N:]
    step = jax.nn.softplus(dt + m["dt_bias"])                # [T, Hm]
    a = -jnp.exp(m["A_log"])
    decay = jnp.exp(segment_sum((step * a).T))               # [Hm, t, s]
    scores = jnp.einsum("tn,sn->ts", c, b, precision=_HI)
    y = jnp.einsum("hts,ts,sh,shp->thp", decay, scores, step, x,
                   precision=_HI) + m["D"][:, None] * x
    y = y.reshape(T, inner) * jax.nn.silu(z)
    return _rms(y, m["norm"], sz.eps)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _attend_row(qkv, sz, quant):
    """Causal grouped-query attention over a row: query head ``i`` meets
    KV head ``i // (H / K)``; no position term."""
    T, HD, KD = qkv.shape[0], sz.H * sz.D, sz.K * sz.D
    q = qkv[:, :HD].reshape(T, sz.K, sz.H // sz.K, sz.D)
    k = qkv[:, HD:HD + KD].reshape(T, sz.K, sz.D)
    v = qkv[:, HD + KD:].reshape(T, sz.K, sz.D)
    scores = _mm("skgd,tkd->kgst", q, k, quant) * sz.att
    seen = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    return _mm("kgst,tkd->skgd", probs, v, quant).reshape(T, HD)


def _blocks(fn, tokens, *args):
    """``fn`` over the arrays ``tokens`` (each ``[n, ...]``, token by
    token), ``BLOCK`` tokens at a time, the last block padded."""
    n = tokens[0].shape[0]
    tokens = [jnp.pad(t, ((0, -n % BLOCK),) + ((0, 0),) * (t.ndim - 1))
              for t in tokens]
    return jnp.concatenate([fn(*(t[s:s + BLOCK] for t in tokens), *args)
                            for s in range(0, n, BLOCK)])[:n]


def _hidden(config, seed, ids, quant):
    """The last layer's output ``[n*T, E]`` of the rows ``ids [n, T]`` laid
    end to end; a layer at a time, each layer's weights made once."""
    sz = sizes(config)
    dtype = jnp.dtype(config["precision"])
    words = seed_words(seed)
    outer = _cast(_outer_in(words, sz, dtype), jnp.float32)
    n, T = ids.shape
    x = sz.emb * outer["embed"][jnp.asarray(ids.reshape(-1))]
    for i, attention in enumerate(sz.attention):
        p = layer_as_served(words, i, sz, dtype)
        mix = _blocks(functools.partial(_mix_in, sz=sz, quant=quant), [x], p)
        if attention:
            rows = [_attend_row(mix[r * T:(r + 1) * T], sz=sz, quant=quant)
                    for r in range(n)]
        else:
            rows = [_mamba_row(mix[r * T:(r + 1) * T], p["mamba"], sz=sz)
                    for r in range(n)]
        x = _blocks(functools.partial(_mix_out, sz=sz, quant=quant),
                    [x, jnp.concatenate(rows)], p)
        del p, mix, rows
    return x, outer, sz


def full_forward(config, seed, ids, quant=False):
    """Logits ``[n, T, V]`` float32 of rows ``ids [n, T]`` (numpy): the
    whole forward at once, for a test's small sizes."""
    x, outer, sz = _hidden(config, seed, ids, quant)
    logits = _mm("te,ve->tv", _rms(x, outer["norm_f"], sz.eps),
                 outer["embed"], quant) / sz.logits
    return np.asarray(logits).reshape(ids.shape + (-1,))


# ------------------------------------------------- serving: teacher forcing
@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _read_rows(x, picks, norm_f, embed, sz, quant):
    """``[BLOCK, 3]``: the best logit, the logit of ``picks`` and the
    arg-best token (exact in float32 below 2^24 rows)."""
    logits = _mm("te,ve->tv", _rms(x, norm_f, sz.eps), embed,
                 quant) / sz.logits
    picked = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
    return jnp.stack([logits.max(-1), picked,
                      jnp.argmax(logits, -1).astype(jnp.float32)], -1)


def teacher_forced(config, seed, ids, picks, quant=False):
    """One forward over ``ids`` [n, T] (right-padded; causal, so the pad
    changes nothing before it) on the weights as served (drawn in float32,
    rounded to the configuration's precision). For each position returns
    the best logit over the vocabulary, the logit of ``picks[n, T]`` and
    the arg-best token, as numpy arrays."""
    x, outer, sz = _hidden(config, seed, ids, quant)
    outs = _blocks(functools.partial(_read_rows, sz=sz, quant=quant),
                   [x, jnp.asarray(picks.reshape(-1))], outer["norm_f"],
                   outer["embed"])
    outs = np.asarray(outs).reshape(ids.shape + (3,))
    return outs[..., 0], outs[..., 1], outs[..., 2].astype(np.int32)


# --------------------------------------------- required operations, bytes
def _weights_a_token(c) -> Tuple[int, int]:
    """Weights a token meets in a Mamba layer and in an attention layer:
    the mixer's matrices (and the convolution's taps) and the MLP."""
    E, I = c["hidden_size"], c["intermediate_size"]
    inner = c["mamba_n_heads"] * c["mamba_d_head"]
    W = inner + 2 * c["mamba_n_groups"] * c["mamba_d_state"]
    D = E // c["num_attention_heads"]
    mlp = 3 * E * I
    mamba = E * (inner + W + c["mamba_n_heads"]) + c["mamba_d_conv"] * W \
        + inner * E
    attention = 2 * E * c["num_attention_heads"] * D \
        + 2 * E * c["num_key_value_heads"] * D
    return mamba + mlp, attention + mlp


def serve_flops(c, prompt_len: int, first: int, last: int) -> float:
    """Required operations to take one request from ``first`` output tokens
    delivered to ``last``, prompt of ``prompt_len`` (the convention of
    ``flops.serve_flops``: the last prompt token and every output token but
    the final one are the inputs that produce an output; the prompt is
    charged with the first output token). A token at position t: twice
    the weights it meets (no lookup), ``4 H D t`` a attention layer
    (scores and weighted sum against t earlier tokens), ``5 Hm P N`` a
    Mamba layer (the state's update and its read); ``2 V E`` where a
    logit is needed."""
    kinds = c["layer_types"]
    n_att = sum(t == "attention" for t in kinds)
    n_mamba = len(kinds) - n_att
    mamba, attention = _weights_a_token(c)
    per_token = 2.0 * (n_mamba * mamba + n_att * attention) + n_mamba * 5.0 \
        * c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
    per_context = 4.0 * n_att * c["num_attention_heads"] * (
        c["hidden_size"] // c["num_attention_heads"])

    def span(a, b):                      # positions a .. b-1
        n = max(0, b - a)
        return n * per_token + per_context * (a + b - 1) * n / 2.0

    total = 2.0 * c["vocab_size"] * c["hidden_size"] * max(0, last - first)
    if last > first:
        lo = 0 if first == 0 else prompt_len + first - 1
        total += span(lo, prompt_len + last - 1)
    return total


def ssm_decode_cost(c, slot_layers: int) -> dict:
    """Operations and HBM bytes of the decode state update over
    ``slot_layers`` states (a slot and a Mamba layer each): the float32
    state read once and written once, unpadded, and ``5`` operations an
    element (decay, the input's product and sum, the read's product and
    sum)."""
    n = c["mamba_n_heads"] * c["mamba_d_head"] * c["mamba_d_state"]
    return {"flops": float(slot_layers * 5 * n),
            "bytes": float(slot_layers * 2 * 4 * n)}
