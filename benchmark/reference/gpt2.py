"""Plain GPT-2 (Radford et al. 2019; the public ``gpt2*`` config.json files).

Straightforward ``jax.numpy`` in float32 with every matrix product at
``Precision.HIGHEST``: no kernels, no cache, no batching tricks. It imports
nothing of the program under test and takes nothing the program made: the
weights come from :func:`make_weights` (seeded, GPT-2's published init
scales), which is also what the benchmark hands to the program.

It works layer by layer (one small jitted block applied ``n_layer`` times,
weights regenerated per layer where only a forward is needed), so the
gpt2-xl forward fits beside nothing else and compiles in seconds.

Departures from the published model, all stated in the configuration files:

* the embedding table has ``padded_vocab_size`` rows; rows past
  ``vocab_size`` are zero, are never drawn as input, and the loss's softmax
  runs over all rows (the program pads the table to a multiple of 128);
* biases and layer-norm parameters are drawn N(0, 0.02) around their
  published start (0 and 1) so that a dropped bias or scale shows.

``quant=True`` is the CONTROL, not a mode of the reference: every matrix
product's operands are rounded to a scaled 8-bit float (e4m3: 3 mantissa
bits, per-tensor scale to the format's maximum 448) — the nearest precision
below bfloat16 that a later PR would be tempted by.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = jax.lax.Precision.HIGHEST
_F8_MAX = 448.0


class Sizes(NamedTuple):
    L: int      # layers
    E: int      # width
    H: int      # heads
    V: int      # vocabulary as published
    Vp: int     # rows of the embedding table as run
    P: int      # positions
    eps: float


def sizes(config) -> Sizes:
    return Sizes(L=int(config["n_layer"]), E=int(config["n_embd"]),
                 H=int(config["n_head"]), V=int(config["vocab_size"]),
                 Vp=int(config["assumed"]["padded_vocab_size"]),
                 P=int(config["n_positions"]),
                 eps=float(config["layer_norm_epsilon"]))


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


def _normal(key, shape, std, mean=0.0):
    return mean + std * jax.random.normal(key, shape, jnp.float32)


def _dense(k1, k2, n_in, n_out, std):
    return {"kernel": _normal(k1, (n_in, n_out), std),
            "bias": _normal(k2, (n_out,), 0.02)}


def _norm(k1, k2, n):
    return {"scale": _normal(k1, (n,), 0.02, 1.0), "bias": _normal(k2, (n,), 0.02)}


def layer_weights(words, layer, sz: Sizes):
    """One block's weights, float32, in the program's tree layout."""
    k = jax.random.split(_key(words, 1, layer), 12)
    E = sz.E
    std_proj = 0.02 / math.sqrt(2 * sz.L)
    return {"ln_1": _norm(k[0], k[1], E),
            "attn": {"qkv": _dense(k[2], k[3], E, 3 * E, 0.02),
                     "proj": _dense(k[4], k[5], E, E, std_proj)},
            "ln_2": _norm(k[6], k[7], E),
            "mlp": {"fc": _dense(k[8], k[9], E, 4 * E, 0.02),
                    "proj": _dense(k[10], k[11], 4 * E, E, std_proj)}}


def outer_weights(words, sz: Sizes):
    k = jax.random.split(_key(words, 0), 4)
    wte = _normal(k[0], (sz.Vp, sz.E), 0.02)
    wte = jnp.where(jnp.arange(sz.Vp)[:, None] < sz.V, wte, 0.0)
    return {"wte": wte, "wpe": _normal(k[1], (sz.P, sz.E), 0.01),
            "ln_f": _norm(k[2], k[3], sz.E)}


def _cast(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def make_weights(words, sz: Sizes, dtype):
    """The whole model in the program's layout (flax names of
    ``GPT2LMHeadModel``), made on the device in one call, in ``dtype``."""
    tree = outer_weights(words, sz)
    for i in range(sz.L):
        tree[f"h_{i}"] = layer_weights(words, i, sz)
    return _cast(tree, dtype)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _layer_as_served(words, layer, sz, dtype):
    return _cast(_cast(layer_weights(words, layer, sz), dtype), jnp.float32)


@functools.partial(jax.jit, static_argnames=("sz", "dtype"))
def _outer_as_served(words, sz, dtype):
    return _cast(_cast(outer_weights(words, sz), dtype), jnp.float32)


# ------------------------------------------------------------------ control
def _f8(x):
    """Round to a scaled e4m3: per-tensor scale to 448, three mantissa bits
    (round to nearest even on the float32 bit pattern). Straight-through
    gradient, as quantised training uses."""
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    y = x * (_F8_MAX / amax)
    bits = jax.lax.bitcast_convert_type(y, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFFF) + ((bits >> 20) & 1)) \
        & jnp.uint32(0xFFF00000)
    y = jnp.clip(jax.lax.bitcast_convert_type(bits, jnp.float32),
                 -_F8_MAX, _F8_MAX) * (amax / _F8_MAX)
    return x + jax.lax.stop_gradient(y - x)


def _mm(spec, a, b, quant):
    if quant:
        a, b = _f8(a), _f8(b)
    return jnp.einsum(spec, a, b, precision=_HI,
                      preferred_element_type=jnp.float32)


# ------------------------------------------------------------------ forward
def _ln(x, p, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, sz: Sizes, quant):
    B, S, E = x.shape
    H, D = sz.H, E // sz.H
    h = _ln(x, p["ln_1"], sz.eps)
    qkv = _mm("bse,ef->bsf", h, p["attn"]["qkv"]["kernel"], quant) \
        + p["attn"]["qkv"]["bias"]
    q, k, v = (t.reshape(B, S, H, D) for t in jnp.split(qkv, 3, -1))
    scores = _mm("bshd,bthd->bhst", q, k, quant) / math.sqrt(D)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    att = _mm("bhst,bthd->bshd", probs, v, quant).reshape(B, S, E)
    x = x + _mm("bse,ef->bsf", att, p["attn"]["proj"]["kernel"], quant) \
        + p["attn"]["proj"]["bias"]
    h = _ln(x, p["ln_2"], sz.eps)
    m = _gelu_new(_mm("bse,ef->bsf", h, p["mlp"]["fc"]["kernel"], quant)
                  + p["mlp"]["fc"]["bias"])
    return x + _mm("bse,ef->bsf", m, p["mlp"]["proj"]["kernel"], quant) \
        + p["mlp"]["proj"]["bias"]


def _embed(ids, wte, wpe):
    return wte[ids] + wpe[None, :ids.shape[1]]


def _logits(x, ln_f, wte, sz: Sizes, quant):
    return _mm("bse,ve->bsv", _ln(x, ln_f, sz.eps), wte, quant)


_block_fwd = jax.jit(_block, static_argnames=("sz", "quant"))
_embed_fwd = jax.jit(_embed)


@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _block_bwd(x, p, dy, sz, quant):
    _, vjp = jax.vjp(lambda x, p: _block(x, p, sz, quant), x, p)
    return vjp(dy)


# ------------------------------------------------- serving: teacher forcing
@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _read_rows(x, ln_f, wte, picks, sz, quant):
    logits = _logits(x, ln_f, wte, sz, quant)[..., :sz.V]
    picked = jnp.take_along_axis(logits, picks[..., None], -1)[..., 0]
    return logits.max(-1), picked, jnp.argmax(logits, -1).astype(jnp.int32)


def teacher_forced(config, seed, ids, picks, quant=False, rows_at_once=8):
    """One forward over ``ids`` [n, T] (right-padded; causal, so the pad
    changes nothing before it) on the weights as served (drawn in float32,
    rounded to the configuration's precision). For each position returns
    the best logit over the published vocabulary, the logit of
    ``picks[n, T]`` and the arg-best token, as numpy arrays. Rows go
    through in blocks of ``rows_at_once`` (one compiled shape), each layer's
    weights made anew from the seed, so that it fits beside nothing."""
    sz = sizes(config)
    dtype = jnp.dtype(config["precision"])
    words = seed_words(seed)
    outer = _outer_as_served(words, sz, dtype)
    n = len(ids)
    pad = (-n) % rows_at_once
    ids = np.concatenate([ids, np.zeros((pad, ids.shape[1]), ids.dtype)])
    picks = np.concatenate([picks, np.zeros((pad, picks.shape[1]), picks.dtype)])
    outs = []
    for r in range(0, len(ids), rows_at_once):
        x = _embed_fwd(jnp.asarray(ids[r:r + rows_at_once]), outer["wte"],
                       outer["wpe"])
        for i in range(sz.L):
            x = _block_fwd(x, _layer_as_served(words, np.int32(i), sz, dtype),
                           sz=sz, quant=quant)
        outs.append([np.asarray(o) for o in _read_rows(
            x, outer["ln_f"], outer["wte"],
            jnp.asarray(picks[r:r + rows_at_once]), sz=sz, quant=quant)])
    return tuple(np.concatenate([o[j] for o in outs])[:n] for j in range(3))


# ------------------------------------------------------ training: three steps
@functools.partial(jax.jit, static_argnames=("sz", "quant"))
def _head_bwd(x, ln_f, wte, labels, n_total, sz, quant):
    """Summed next-token cross-entropy of some rows over ``n_total``
    predicted tokens, and its gradients."""
    def f(x, ln_f, wte):
        logits = _logits(x[:, :-1], ln_f, wte, sz, quant)
        lse = jax.scipy.special.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
        return jnp.sum(lse - gold) / n_total
    return jax.value_and_grad(f, (0, 1, 2))(x, ln_f, wte)


@jax.jit
def _embed_bwd(ids, dx, dwte, wpe):
    dwpe = jnp.zeros_like(wpe).at[:ids.shape[1]].add(dx.sum(0))
    return dwte.at[ids].add(dx), dwpe


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adam(params, grads, mu, nu, t, lr, b1, b2, eps):
    """Adam (Kingma & Ba 2015, algorithm 1), no weight decay."""
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t))
        / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
    return params, mu, nu


def _parts(tree):
    """The tree as it is compared: the fused q/k/v leaves split into their
    thirds. The key's bias has no gradient under softmax (it shifts every
    score of a row alike) and moves under Adam by round-off alone; fused
    with the query's and the value's it would hide from the rule that
    leaves such leaves out of the change."""
    def split(node):
        if not isinstance(node, dict):
            return node
        out = {}
        for name, sub in node.items():
            if name == "qkv":
                out[name] = {f"{leaf}_{part}": piece
                             for leaf, x in sub.items()
                             for part, piece in zip("qkv", jnp.split(x, 3, -1))}
            else:
                out[name] = split(sub)
        return out
    return split(dict(tree))


@jax.jit
def leaf_norms(tree):
    """L2 norm of every compared part, float32, in the order of
    :func:`leaf_names`."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(_parts(tree))])


@functools.partial(jax.jit, static_argnames=("n",))
def leaf_samples(tree, n=4096):
    """The first ``n`` elements of every compared part (zero-padded), as
    one [parts, n] float32 array: enough of a gradient to measure how far
    two of them point apart, without keeping a second copy of it."""
    rows = []
    for x in jax.tree.leaves(_parts(tree)):
        head = x.astype(jnp.float32).reshape(-1)[:n]
        rows.append(jnp.pad(head, (0, n - head.shape[0])))
    return jnp.stack(rows)


@jax.jit
def leaf_norms_of_difference(a, b):
    return leaf_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b))


def leaf_names(tree):
    return ["/".join(str(getattr(k, "key", k)) for k in path) for path, _ in
            jax.tree_util.tree_flatten_with_path(_parts(tree))[0]]


class TrainReference:
    """Follows the program's first optimizer steps on the same batches:
    the mean next-token cross-entropy of the whole global batch, its
    gradient, Adam on float32 weights."""

    def __init__(self, config, optimizer, seed, quant=False, head_rows=2):
        self.sz = sizes(config)
        self.quant = quant
        self.head_rows = head_rows
        self.hp = {"lr": float(optimizer["lr"]),
                   "b1": float(optimizer.get("betas", (0.9, 0.999))[0]),
                   "b2": float(optimizer.get("betas", (0.9, 0.999))[1]),
                   "eps": float(optimizer.get("eps", 1e-8))}
        words = seed_words(seed)
        self.start = make_weights(words, self.sz, jnp.float32)
        self.params = jax.tree.map(jnp.copy, self.start)
        self.mu = jax.tree.map(jnp.zeros_like, self.params)
        self.nu = jax.tree.map(jnp.zeros_like, self.params)
        self.t = 0

    def loss_and_grads(self, ids):
        sz, q, p = self.sz, self.quant, self.params
        ids = jnp.asarray(ids, jnp.int32)
        B, S = ids.shape
        xs = [_embed_fwd(ids, p["wte"], p["wpe"])]
        for i in range(sz.L):
            xs.append(_block_fwd(xs[-1], p[f"h_{i}"], sz=sz, quant=q))
        loss = 0.0
        dxs, dln_f, dwte = [], None, None
        for r in range(0, B, self.head_rows):
            rows = slice(r, r + self.head_rows)
            part, (dx, g_ln, g_wte) = _head_bwd(
                xs[-1][rows], p["ln_f"], p["wte"], ids[rows, 1:],
                np.float32(B * (S - 1)), sz=sz, quant=q)
            loss = loss + part
            dxs.append(dx)
            dln_f = g_ln if dln_f is None else jax.tree.map(jnp.add, dln_f, g_ln)
            dwte = g_wte if dwte is None else dwte + g_wte
        dx = jnp.concatenate(dxs)
        grads = {"ln_f": dln_f}
        for i in reversed(range(sz.L)):
            dx, grads[f"h_{i}"] = _block_bwd(xs[i], p[f"h_{i}"], dx,
                                             sz=sz, quant=q)
            xs[i + 1] = None        # free the activations as they are used
        grads["wte"], grads["wpe"] = _embed_bwd(ids, dx, dwte, p["wpe"])
        return loss, grads

    def step(self, ids):
        """One optimizer step; returns the loss (device scalar), the
        gradient's per-leaf norms and its per-leaf samples."""
        loss, grads = self.loss_and_grads(ids)
        self.t += 1
        gnorms = (leaf_norms(grads), leaf_samples(grads))
        hp = self.hp
        self.params, self.mu, self.nu = _adam(
            self.params, grads, self.mu, self.nu, np.float32(self.t),
            np.float32(hp["lr"]), np.float32(hp["b1"]), np.float32(hp["b2"]),
            np.float32(hp["eps"]))
        return loss, gnorms

    def change_norms(self):
        return leaf_norms_of_difference(self.params, self.start)
