"""Plain language model of ``NVIDIA-Nemotron-3-Nano-30B-A3B-BF16`` (nvidia;
the public ``config.json``, ``model_type`` ``nemotron_h``): a decoder whose
every layer is ONE part, as ``hybrid_override_pattern`` says, RMS-normed
and added to the residual:

* ``M``, a Mamba-2 mixer: ``[z | xBC | dt] = h W_in``; ``xBC`` through a
  causal depthwise convolution of ``conv_kernel`` taps with its bias and a
  SiLU, then split into the heads' inputs ``x [heads x head_dim]``, and
  ``B`` and ``C`` of ``n_groups`` groups of ``ssm_state_size`` (head ``h``
  reads group ``h // (heads / groups)``); ``dt = softplus(dt + dt_bias)``,
  ``A = -exp(A_log)``; a head's state ``S <- exp(dt A) S + dt x B^T``, its
  output ``S C + D x``; gated by ``silu(z)``, RMS-normed over each group's
  channels with one gain, projected back;
* ``*``, grouped-query attention without positions (the ``nemotron_h``
  modelling code applies no rotary embedding), scores scaled by
  ``head_dim ** -0.5``;
* ``E``, experts: ``s = sigmoid(h W_r)`` in float32 over all the router's
  outputs, the top ``num_experts_per_tok`` of ``s + b_corr`` chosen (the
  bias for the choice only), ``w_i = routed_scaling_factor s_i / sum s``;
  ``sum_i w_i relu(h U_i)^2 D_i`` over the experts held here, plus the
  shared expert ``relu(h U_s)^2 D_s``.

The embedding is not scaled; a final RMSNorm and the untied head follow the
last layer.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``. It imports nothing of the program under test and
takes nothing the program made: the weights come from :func:`make_weights`
(seeded), which is also what the benchmark hands to the program.

It works a layer at a time (each layer's weights made once from the seed),
a row at a time inside a layer, and ``BLOCK`` tokens of a row a program,
so that no program's shape follows a row's length (the keys of an
attention layer are padded to a multiple of ``KEYS``): a Mamba layer
carries the row's state and the convolution's last inputs from block to
block, and computes a block in the DUAL form, the exponent a masked
segment sum down the block (not a difference of cumulative sums), the
state before the block decayed into it; an expert layer gathers the
tokens that chose each held expert, up to a capacity read from the
routing before the block is computed (a power of two, so that no token is
dropped and few programs compile), and loops over no expert. A row's pad
(right of its tokens) changes nothing before it: every part is causal.

It is given the SHARE of the model that the configuration file states
(``deployment``): the routed experts ``experts_held`` of each expert layer
(the router keeps its published width; what the absent experts would add
is left out), the vocabulary slice, and the file's depth.

Departures from the published description, each stated in the
configuration file (``assumed``): no positions in attention; the
convolution's weight held ``[taps, channels]`` (tap ``k`` meets the input
``taps - 1 - k`` tokens back; published ``[channels, 1, taps]``); a
routed expert's ``down`` held ``[width, hidden]`` (published ``down_proj``
``[hidden, width]``; its ``up`` as published, ``[width, hidden]``); the
weights drawn as ``assumed`` says.

``quant=True`` is the CONTROL, not a mode of the reference: the operands of
every matrix product that the configuration states in bfloat16 (the
projections, the experts, attention's two products, the head) are rounded
to a scaled 8-bit float (e4m3: 3 mantissa bits, per-tensor scale to the
format's maximum 448), the nearest precision below bfloat16. The router's
product and the state's arithmetic stay float32 there too, as the
configuration states them.
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
BLOCK = 512             # tokens of a row that one program takes
KEYS = 1024             # an attention layer's keys are padded to a multiple
CAPACITIES = (32, 64, 128, 256, BLOCK)  # tokens an expert takes in a block


class Sizes(NamedTuple):
    kinds: Tuple[str, ...]      # a layer's part: "M", "*" or "E"
    E: int
    H: int              # query heads
    K: int              # key/value heads
    D: int              # head size
    Hm: int             # Mamba heads
    P: int              # Mamba head size
    N: int              # ssm_state_size
    G: int              # groups of B and C
    taps: int
    V: int              # vocabulary rows held
    eps: float
    X: int              # router outputs (published experts)
    held: Tuple[int, int]   # [first, stop) of them held here
    k: int              # experts a token
    route_scale: float
    M: int              # routed expert width
    S: int              # shared expert width
    dt_init: Tuple[float, float, float]     # min, max, floor

    @property
    def inner(self) -> int:
        return self.Hm * self.P

    @property
    def channels(self) -> int:
        return self.inner + 2 * self.G * self.N

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]


def sizes(config) -> Sizes:
    kinds = tuple(config["hybrid_override_pattern"])
    assert len(kinds) == config["num_hidden_layers"], kinds
    assert set(kinds) <= {"M", "*", "E"}, kinds
    assert config["n_group"] == config["topk_group"] == 1, \
        "group-limited routing: not written"
    assert config["mlp_hidden_act"] == "relu2", config["mlp_hidden_act"]
    held = tuple(int(e) for e in config["deployment"]["experts_held"])
    assert held[1] - held[0] == config["n_routed_experts"], held
    return Sizes(
        kinds=kinds, E=int(config["hidden_size"]),
        H=int(config["num_attention_heads"]),
        K=int(config["num_key_value_heads"]), D=int(config["head_dim"]),
        Hm=int(config["mamba_num_heads"]), P=int(config["mamba_head_dim"]),
        N=int(config["ssm_state_size"]), G=int(config["n_groups"]),
        taps=int(config["conv_kernel"]), V=int(config["vocab_size"]),
        eps=float(config["layer_norm_epsilon"]),
        X=int(config["published"]["n_routed_experts"]), held=held,
        k=int(config["num_experts_per_tok"]),
        route_scale=float(config["routed_scaling_factor"]),
        M=int(config["moe_intermediate_size"]),
        S=int(config["moe_shared_expert_intermediate_size"]),
        dt_init=(float(config["time_step_min"]),
                 float(config["time_step_max"]),
                 float(config["time_step_floor"])))


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


def layer_weights(words, layer, sz: Sizes, kind: str):
    """One layer's weights, float32, in the program's tree layout: matrices
    N(0, 0.02), norm gains N(1, 0.02), the router's selection bias
    N(0, 0.02); Mamba-2's published initialisation from the
    configuration's own ``time_step_*``: a step ``dt`` log-uniform on
    [min, max] and at least ``floor``, ``dt_bias`` its inverse softplus,
    ``A_log = log U[1, 16]``, ``D = 1``; the convolution's weight and bias
    U(-1/2, 1/2) (a depthwise ``Conv1d``'s default over 4 taps)."""
    k = iter(jax.random.split(_key(words, 1, layer), 16))
    E = sz.E
    p = {"norm_1": _normal(next(k), (E,), 1.0)}
    if kind == "*":
        p["attn"] = {"q": _normal(next(k), (E, sz.H * sz.D)),
                     "k": _normal(next(k), (E, sz.K * sz.D)),
                     "v": _normal(next(k), (E, sz.K * sz.D)),
                     "o": _normal(next(k), (sz.H * sz.D, E))}
    elif kind == "E":
        p["moe"] = {"router": _normal(next(k), (E, sz.X)),
                    "router_bias": _normal(next(k), (sz.X,)),
                    "shared": {"up": _normal(next(k), (E, sz.S)),
                               "down": _normal(next(k), (sz.S, E))},
                    "experts": {
                        "up": _normal(next(k), (sz.n_held, sz.M, E)),
                        "down": _normal(next(k), (sz.n_held, sz.M, E))}}
    else:
        lo, hi, floor = sz.dt_init
        step = jnp.maximum(jnp.exp(_uniform(next(k), (sz.Hm,), math.log(lo),
                                            math.log(hi))), floor)
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
    k = jax.random.split(_key(words, 0), 3)
    return {"embed": _normal(k[0], (sz.V, sz.E)),
            "head": _normal(k[1], (sz.V, sz.E)),
            "norm_f": _normal(k[2], (sz.E,), 1.0)}


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, static_argnames=("sz", "kind", "dtype"))
def _layer_in(words, layer, sz, kind, dtype):
    return _cast(layer_weights(words, layer, sz, kind), dtype)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _outer_in(words, sz, dtype):
    return _cast(outer_weights(words, sz), dtype)


def make_weights(words, sz: Sizes, dtype):
    """The whole share in the program's layout (the tree of
    ``deepspeed_tpu.models.ssm_hybrid`` for a layer of one part), made on
    the device in ``dtype``, a layer a call."""
    tree = _outer_in(words, sz, dtype)
    for i, kind in enumerate(sz.kinds):
        tree[f"h_{i}"] = _layer_in(words, np.int32(i), sz, kind, dtype)
    return tree


def layer_as_served(words, layer, sz, dtype):
    """Drawn in float32, rounded to the configuration's precision, and back:
    the values the program holds."""
    return _cast(_layer_in(words, np.int32(layer), sz, sz.kinds[layer],
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


def _rounded(x, quant):
    """``x`` as the control rounds it (``quant`` True), or to the dtype that
    ``quant`` names (``"bfloat16"``: tests/perf/route_flips.py)."""
    return _f8(x) if quant is True else x.astype(quant).astype(jnp.float32)


def _mm(spec, a, b, quant):
    if quant:
        a, b = _rounded(a, quant), _rounded(b, quant)
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ forward
def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def segment_sum(a):
    """``a [H, T]`` -> ``[H, T, T]`` with ``[h, t, s] = sum_{s<r<=t}
    a[h, r]`` for ``s <= t`` and ``-inf`` above the diagonal: ``a`` laid
    along ``s``, masked to ``r > s`` and summed down ``t``."""
    T = a.shape[-1]
    r = jnp.arange(T)
    along = jnp.where(r[:, None] > r[None, :], a[:, :, None], 0.0)
    summed = jnp.cumsum(along, axis=1)
    return jnp.where(r[:, None] >= r[None, :], summed, -jnp.inf)


def _ssd(x, dt, a, b, c, s0, sz: Sizes):
    """The state-space mixer over ``T`` tokens from the state ``s0 [Hm, P,
    N]`` before them, in its dual form: x ``[T, Hm, P]``, dt ``[T, Hm]``,
    a ``[Hm]``, b and c ``[T, G, N]``. Returns ``y [T, Hm, P]`` (no ``D``)
    and the state after the last token."""
    T = x.shape[0]
    bh = jnp.repeat(b, sz.Hm // sz.G, axis=1)               # [T, Hm, N]
    ch = jnp.repeat(c, sz.Hm // sz.G, axis=1)
    la = (dt * a).T                                         # [Hm, T]
    seg = segment_sum(la)                                   # [Hm, t, s]
    scores = jnp.einsum("thn,shn->hts", ch, bh, precision=_HI)
    y = jnp.einsum("hts,hts,sh,shp->thp", jnp.exp(seg), scores, dt, x,
                   precision=_HI)
    # the state before the block reaches token t decayed by sum_{r<=t}
    r = jnp.arange(T)
    upto = (r[:, None] >= r[None, :]).astype(jnp.float32)
    into = jnp.exp(jnp.einsum("tr,hr->th", upto, la, precision=_HI))
    y = y + into[:, :, None] * jnp.einsum("thn,hpn->thp", ch, s0,
                                          precision=_HI)
    # token s reaches the end by sum_{s<r<=T-1}: the segment sum's last row
    to_end = jnp.exp(seg[:, -1, :]).T * dt                  # [T, Hm]
    s_end = into[-1][:, None, None] * s0 + jnp.einsum(
        "sh,shp,shn->hpn", to_end, x, bh, precision=_HI)
    return y, s_end


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _mamba_block(x, state, tail, p, sz, quant):
    """``BLOCK`` tokens of a row through a Mamba-2 layer, from the row's
    state and the convolution's last ``taps - 1`` inputs before them
    (zeros before the row's first token). Returns the rows after the layer,
    the state and the inputs the next block's convolution needs."""
    T = x.shape[0]
    m = p["mamba"]
    inner, W, Hm, P, N, G = sz.inner, sz.channels, sz.Hm, sz.P, sz.N, sz.G
    proj = _mm("te,ef->tf", _rms(x, p["norm_1"], sz.eps), m["in_proj"],
               quant)
    z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + W],
                  proj[:, inner + W:])
    window = jnp.concatenate([tail, xbc])
    conv = jax.nn.silu(m["conv_b"] + sum(
        window[j:j + T] * m["conv_w"][j] for j in range(sz.taps)))
    xs = conv[:, :inner].reshape(T, Hm, P)
    b = conv[:, inner:inner + G * N].reshape(T, G, N)
    c = conv[:, inner + G * N:].reshape(T, G, N)
    step = jax.nn.softplus(dt + m["dt_bias"])
    y, state = _ssd(xs, step, -jnp.exp(m["A_log"]), b, c, state, sz)
    y = (y + m["D"][:, None] * xs).reshape(T, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(T, G, -1), m["norm"].reshape(G, -1),
             sz.eps).reshape(T, inner)
    return x + _mm("tf,fe->te", y, m["out_proj"], quant), state, \
        window[T:]


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _qkv(x, p, sz, quant):
    h = _rms(x, p["norm_1"], sz.eps)
    a = p["attn"]
    return [_mm("te,ef->tf", h, a[n], quant) for n in ("q", "k", "v")]


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _attend_block(x, q, first, keys, values, p, sz, quant):
    """``BLOCK`` queries of a row at positions ``first..`` over the row's
    keys and values ``[T', K*D]`` (padded past the row: a pad key lies
    after every query and is masked), query head ``i`` on KV head ``i //
    (H / K)``, no position term; the output projection onto the
    residual."""
    T = q.shape[0]
    q = q.reshape(T, sz.K, sz.H // sz.K, sz.D)
    keys = keys.reshape(-1, sz.K, sz.D)
    values = values.reshape(-1, sz.K, sz.D)
    scores = _mm("skgd,tkd->kgst", q, keys, quant) * sz.D ** -0.5
    seen = jnp.arange(keys.shape[0])[None, :] \
        <= first + jnp.arange(T)[:, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    o = _mm("kgst,tkd->skgd", probs, values, quant).reshape(T, -1)
    return x + _mm("tf,fe->te", o, p["attn"]["o"], quant)


def _route(h, m, sz: Sizes):
    """The chosen experts ``[T, k]`` (of all the router's outputs) and
    their weights; float32, never quantised."""
    scores = jax.nn.sigmoid(jnp.einsum("te,ex->tx", h, m["router"],
                                       precision=_HI))
    chosen = jax.lax.top_k(scores + m["router_bias"], sz.k)[1]
    w = jnp.take_along_axis(scores, chosen, 1)
    return chosen, w / (w.sum(-1, keepdims=True) + 1e-20) * sz.route_scale


def _members(h, m, sz: Sizes):
    """``[T, held]``: whether token t chose held expert e, and its weight."""
    chosen, w = _route(h, m, sz)
    hit = (chosen - sz.held[0])[:, :, None] == jnp.arange(sz.n_held)
    return hit.any(1), (w[:, :, None] * hit).sum(1)


@functools.partial(jax.jit, static_argnames=("sz",))
def _expert_load(x, p, sz):
    """The most tokens of the block that any one held expert takes."""
    member, _ = _members(_rms(x, p["norm_1"], sz.eps), p["moe"], sz)
    return member.sum(0).max()


@functools.partial(jax.jit, static_argnames=("sz", "cap", "quant"))
def _expert_block(x, p, sz, cap, quant, route=None):
    """``BLOCK`` tokens through an expert layer: each held expert takes the
    tokens that chose it (gathered, up to ``cap``: at least the most any
    expert takes), ``relu(h U)^2 D`` weighted and added back; the shared
    expert over every token; onto the residual. ``route``: other rows of
    the block whose choices and weights it takes instead of ``x``'s (a
    measurement's pinned routing, tests/perf/route_flips.py)."""
    T = x.shape[0]
    m = p["moe"]
    h = _rms(x, p["norm_1"], sz.eps)
    member, weight = _members(
        h if route is None else _rms(route, p["norm_1"], sz.eps), m, sz)
    idx = jax.vmap(lambda col: jnp.nonzero(col, size=cap, fill_value=T)[0],
                   in_axes=1)(member)                       # [held, cap]
    rows = jnp.concatenate([h, jnp.zeros((1, sz.E))])[idx]
    up = _mm("ecd,emd->ecm", rows, m["experts"]["up"], quant)
    out = _mm("ecm,emd->ecd", jnp.square(jax.nn.relu(up)),
              m["experts"]["down"], quant)
    w = jnp.concatenate([weight, jnp.zeros((1, sz.n_held))])[
        idx, jnp.arange(sz.n_held)[:, None]]
    routed = jnp.zeros((T + 1, sz.E)).at[idx].add(out * w[..., None])[:T]
    shared = _mm("tm,md->td", jnp.square(jax.nn.relu(_mm(
        "te,em->tm", h, m["shared"]["up"], quant))), m["shared"]["down"],
        quant)
    return x + routed + shared


def _capacity(load: int) -> int:
    return next(c for c in CAPACITIES if c >= load)


def _layer_row(x, p, kind, sz: Sizes, quant):
    """One layer over one row ``x [T, E]`` (``T`` a multiple of
    ``BLOCK``), ``BLOCK`` tokens a program."""
    starts = range(0, x.shape[0], BLOCK)
    if kind == "M":
        state = jnp.zeros((sz.Hm, sz.P, sz.N))
        tail = jnp.zeros((sz.taps - 1, sz.channels))
        out = []
        for s in starts:
            y, state, tail = _mamba_block(x[s:s + BLOCK], state, tail, p,
                                          sz=sz, quant=quant)
            out.append(y)
        return jnp.concatenate(out)
    if kind == "E":
        loads = [_expert_load(x[s:s + BLOCK], p, sz=sz) for s in starts]
        cap = _capacity(int(jnp.max(jnp.stack(loads))))    # one wait
        return jnp.concatenate([_expert_block(x[s:s + BLOCK], p, sz=sz,
                                              cap=cap, quant=quant)
                                for s in starts])
    qkv = [_qkv(x[s:s + BLOCK], p, sz=sz, quant=quant) for s in starts]
    width = -(-x.shape[0] // KEYS) * KEYS
    keys, values = (jnp.pad(jnp.concatenate([part[j] for part in qkv]),
                            ((0, width - x.shape[0]), (0, 0)))
                    for j in (1, 2))
    return jnp.concatenate([
        _attend_block(x[s:s + BLOCK], part[0], np.int32(s), keys, values, p,
                      sz=sz, quant=quant) for s, part in zip(starts, qkv)])


def _hidden_rows(config, seed, ids, quant):
    """The last layer's output of every row of ``ids [n, T]`` (``T`` a
    multiple of ``BLOCK``), one array a row, with the outer weights as
    served: the layers outermost, each layer's weights made once."""
    sz = sizes(config)
    dtype = jnp.dtype(config["precision"])
    words = seed_words(seed)
    outer = _cast(_outer_in(words, sz, dtype), jnp.float32)
    xs = [outer["embed"][jnp.asarray(row)] for row in ids]
    for i, kind in enumerate(sz.kinds):
        p = layer_as_served(words, i, sz, dtype)
        xs = [_layer_row(x, p, kind, sz, quant) for x in xs]
        del p
    return xs, outer, sz


def _padded(a, T):
    return np.pad(a, ((0, 0), (0, -T % BLOCK)))


def full_forward(config, seed, ids, quant=False):
    """Logits ``[n, T, V]`` float32 of rows ``ids [n, T]`` (numpy): the
    whole forward at once, for a test's small sizes."""
    T = ids.shape[1]
    xs, outer, sz = _hidden_rows(config, seed, _padded(ids, T), quant)
    return np.stack([np.asarray(_mm(
        "te,ve->tv", _rms(x[:T], outer["norm_f"], sz.eps), outer["head"],
        quant)) for x in xs])


# ------------------------------------------------- serving: teacher forcing
@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _read_rows(x, norm_f, head, picks, sz, quant):
    """``[BLOCK, 3]``: the best logit, the logit of ``picks`` and the
    arg-best token (exact in float32 below 2^24 rows)."""
    logits = _mm("te,ve->tv", _rms(x, norm_f, sz.eps), head, quant)
    picked = jnp.take_along_axis(logits, picks[:, None], -1)[:, 0]
    return jnp.stack([logits.max(-1), picked,
                      jnp.argmax(logits, -1).astype(jnp.float32)], -1)


def teacher_forced(config, seed, ids, picks, quant=False):
    """One forward over ``ids`` [n, T] (right-padded; causal, so the pad
    changes nothing before it) on the weights as served (drawn in float32,
    rounded to the configuration's precision). For each position returns
    the best logit over the vocabulary held, the logit of ``picks[n, T]``
    and the arg-best token, as numpy arrays."""
    T = ids.shape[1]
    xs, outer, sz = _hidden_rows(config, seed, _padded(ids, T), quant)
    picks = _padded(picks, T)
    outs = np.stack([np.concatenate([np.asarray(_read_rows(
        x[s:s + BLOCK], outer["norm_f"], outer["head"],
        jnp.asarray(row[s:s + BLOCK]), sz=sz, quant=quant))
        for s in range(0, x.shape[0], BLOCK)])[:T]
        for x, row in zip(xs, picks)])
    return outs[..., 0], outs[..., 1], outs[..., 2].astype(np.int32)


# --------------------------------------------- required operations, bytes
def _weights_a_token(c) -> dict:
    """Weights a token meets in a layer of each part: the Mamba mixer's
    matrices and the convolution's taps; attention's four; the router,
    the shared expert and the EXPECTED share of routed experts held here
    (``num_experts_per_tok`` x held / published of one expert: the
    router's choices fall on this chip's experts in that share)."""
    E = c["hidden_size"]
    inner = c["mamba_num_heads"] * c["mamba_head_dim"]
    W = inner + 2 * c["n_groups"] * c["ssm_state_size"]
    HD = c["num_attention_heads"] * c["head_dim"]
    KD = c["num_key_value_heads"] * c["head_dim"]
    X = c["published"]["n_routed_experts"]
    share = c["num_experts_per_tok"] * c["n_routed_experts"] / X
    return {"M": E * (inner + W + c["mamba_num_heads"])
            + c["conv_kernel"] * W + inner * E,
            "*": 2 * E * HD + 2 * E * KD,
            "E": E * X + 2 * E * c["moe_shared_expert_intermediate_size"]
            + share * 2 * E * c["moe_intermediate_size"]}


def serve_flops(c, prompt_len: int, first: int, last: int) -> float:
    """Required operations to take one request from ``first`` output tokens
    delivered to ``last``, prompt of ``prompt_len`` (the convention of
    ``flops.serve_flops``: the last prompt token and every output token but
    the final one are the inputs that produce an output; the prompt is
    charged with the first output token). A token at position t: twice
    the weights it meets (the held experts' work only, at their expected
    share; no lookup), ``5 Hm P N`` a Mamba layer (the state's update and
    its read), ``4 H D t`` an attention layer (scores and weighted sum
    against t earlier tokens); ``2 V E`` where a logit is needed, over the
    vocabulary held."""
    kinds = c["hybrid_override_pattern"]
    weights = _weights_a_token(c)
    per_token = 2.0 * sum(weights[kind] for kind in kinds) \
        + kinds.count("M") * 5.0 * c["mamba_num_heads"] \
        * c["mamba_head_dim"] * c["ssm_state_size"]
    per_context = 4.0 * kinds.count("*") * c["num_attention_heads"] \
        * c["head_dim"]

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
    state read once and written once, unpadded, and the slot's ``B`` and
    ``C`` of every group read once (float32); ``5`` operations an element
    of the state (decay, the input's product and sum, the read's product
    and sum)."""
    n = c["mamba_num_heads"] * c["mamba_head_dim"] * c["ssm_state_size"]
    groups = 2 * c["n_groups"] * c["ssm_state_size"]
    return {"flops": float(slot_layers * 5 * n),
            "bytes": float(slot_layers * 4 * (2 * n + groups))}

