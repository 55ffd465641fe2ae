"""Paged model runner — the one forward pass behind the server's programs.

The flax decode path (models/gpt2.py ``decode=True``) owns a per-batch
contiguous cache with ONE shared ``cache_index`` — every sequence in the
batch must sit at the same position, which is exactly what continuous
batching breaks. This runner re-expresses a model's math directly over
its *params pytree* with per-slot positions and the paged pool from
serving/kv_cache.py, ONCE: :meth:`PagedRunner._forward` embeds ``[B, C]``
tokens at their own positions, walks the blocks, attends over each
slot's past pages plus the chunk itself, writes the cache rows of every
layer that ran in one scatter per pool, and returns the logits. The
model's configuration says which of the three block definitions the
forward walks (no option and no model's name):

* GPT-2's (:class:`_GPT2Blocks`): LayerNorm, fused QKV with heads of
  ``D`` lanes, learned positions, GELU MLP, tied head; K and V pools.
* latent attention with experts (:class:`_MLAMoEBlocks`;
  models/mla_moe.py): RMSNorm, low-rank queries, ONE latent row a token
  (the cache holds nothing else, so every attention runs in the latent
  space with the key's up-projection absorbed into the query), rotary
  positions at each token's own offset (YaRN frequencies), SwiGLU, and
  the expert layer of moe/held_experts.py over the experts this chip
  holds; untied head; one ``kv`` pool.
* the state-space hybrid (:class:`_SSMHybridBlocks`; models/ssm_hybrid.py):
  RMSNorm, and as ``layer_types`` says a Mamba-2 mixer with grouped ``B``
  and ``C``, whose per-slot state (ops/ssm) a layer reads and writes back
  where it lies inside the layer loop, grouped-query attention with no
  positions over K and V pools that hold the attention layers alone, or
  an expert layer (the one the latent block runs, of the experts' kind
  the configuration names); each mixer followed by a SwiGLU MLP, or each
  layer its one part; tied or untied head.

The four compiled programs are the forward's callers:

* ``decode_step`` — the one static-shaped program the server calls every
  iteration: the forward at ``C = 1``, then a sampled token per request
  (serving/sampling.py), ``decode_steps`` times in one dispatch.
  Compiled once for the whole serving lifetime — request churn only
  changes tensor *values*.
* ``prefill_chunk`` — fills prompt KV ``chunk`` tokens at a time
  (serving/prefill.py plans the chunks) so a long prompt never stalls
  the decode batch: a step's chunks ``R`` at a time, one slot a row, the
  forward at ``B = R``, no head (at ``R = 1`` the lone-slot form, with no
  row dimension). Also compiled once: a short chunk is padded, the last
  dispatch of a step with pad rows, and their writes are routed to the
  null block.
* the speculative draft and verify programs (serving/speculative.py):
  the forward at ``C = 1`` over a layer prefix, and at ``C = K+1``.

Which attention runs is read off the static shape, not an option: one
query a slot goes to ``paged_decode_attention`` (the Pallas kernel on a
TPU over bfloat16 pools under one device, else the jnp walk), a chunk to
the jnp walk (serving/paged_attention.py states the rule). So for the
state: one token a slot is ``ssm_decode`` (or its jnp form), a chunk the
chunked scan.

Weight formats: float kernels and the engine's TRUE int8 weight storage
(module_quantize ``quant_scales`` collection) both work — the dequant
folds into the matmul exactly like QuantDense. The int8 *KV* layout is
the cache's concern and composes transparently.

What is not served is refused at construction with an error that names
the mechanism (:class:`ServingNotSupported`), never mid-step: a GPT-2
tree with rotary positions or experts in its blocks, pipeline stages,
ring / Ulysses / block-sparse attention; over a latent cache, int8
pools and int8 weights; for a model with per-slot state, groups of ``B``
and ``C`` whose heads do not fill whole 128-lane rows of the packed state;
the server refuses what it composes with.
"""

import functools

import jax
import jax.numpy as jnp

from deepspeed_tpu.moe.held_experts import held_expert_mlp, route
from deepspeed_tpu.ops import ssm
from deepspeed_tpu.ops.quantizer.int8_linear import int8_matmul
from deepspeed_tpu.ops.transformer.decode import quantize_kv
from deepspeed_tpu.serving.paged_attention import (paged_chunk_attention,
                                                   paged_decode_attention)
from deepspeed_tpu.serving.sampling import sample_tokens

_LN_EPS = 1e-5


def _ln(x, p):
    """nn.LayerNorm(epsilon=1e-5) parity (fast-variance form)."""
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(x * x, axis=-1, keepdims=True) - mu * mu, 0.0)
    y = (x - mu) * jax.lax.rsqrt(var + _LN_EPS)
    return y * p["scale"] + p["bias"]


def _dense(x, p, scales=None):
    """QuantDense parity: float kernels matmul directly; int8 kernels
    fold the per-column scale into the matmul."""
    kernel = p["kernel"]
    bias = p.get("bias")
    if kernel.dtype == jnp.int8:
        return int8_matmul(x, kernel, scales["kernel_scale"], bias)
    y = x @ kernel
    if bias is not None:
        y = y + bias.astype(y.dtype)
    return y


def _sub(scales, *path):
    """Descend the quant_scales mirror (may be absent)."""
    node = scales
    for seg in path:
        if not isinstance(node, dict) or seg not in node:
            return None
        node = node[seg]
    return node


class ServingNotSupported(NotImplementedError):
    """The model or the configuration asks for a mechanism that the
    server does not run; raised where the server is built."""


def serves_latent(cfg) -> bool:
    """Whether ``cfg`` describes latent attention (one cached latent a
    token: models/mla_moe.py), read from what it declares."""
    return hasattr(cfg, "kv_lora_rank")


def serves_state(cfg) -> bool:
    """Whether ``cfg`` describes layers that carry a per-slot state
    (Mamba-2: models/ssm_hybrid.py), read from what it declares."""
    return hasattr(cfg, "mamba_layers")


def _require_gpt2_like(cfg):
    for attr in ("n_layer", "n_head", "n_embd", "n_positions",
                 "vocab_size"):
        if not hasattr(cfg, attr):
            raise ServingNotSupported(
                f"serving needs a GPT2Config-like or a latent-attention "
                f"model config, or a state-space hybrid (missing "
                f"{attr!r}); got {type(cfg).__name__}")


def cache_rows(cfg) -> dict:
    """What ``PagedKVCache`` needs to know of the model's cache rows (and,
    with per-slot state, of each slot's state a layer)."""
    if serves_latent(cfg):
        return dict(n_head=cfg.num_attention_heads,
                    head_dim=cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                    latent_width=cfg.latent_width)
    if serves_state(cfg):
        rows = ssm.packed_rows(cfg.mamba_n_heads, cfg.mamba_d_head)
        taps = cfg.mamba_d_conv - 1
        return dict(n_head=cfg.num_key_value_heads, head_dim=cfg.head_dim,
                    slot_state={
                        "ssm": ((rows, cfg.mamba_d_state, ssm.scan.LANES),
                                jnp.float32),
                        "conv": ((taps * cfg.conv_channels,), None)})
    _require_gpt2_like(cfg)
    return dict(n_head=cfg.n_head, head_dim=cfg.n_embd // cfg.n_head)


def cache_layers(cfg) -> dict:
    """Layers whose tokens the paged pools hold, and layers that carry a
    per-slot state: ``{"paged": n, "per_slot": m}``."""
    if serves_state(cfg):
        return {"paged": len(cfg.attention_layers),
                "per_slot": len(cfg.mamba_layers)}
    return {"paged": cfg.n_layer, "per_slot": 0}


class _GPT2Blocks:
    """GPT-2's embedding, block and head over the params pytree of
    ``GPT2LMHeadModel``; K and V pools with the heads in lanes."""

    def __init__(self, cfg, cache):
        _require_gpt2_like(cfg)
        if getattr(cfg, "position_embedding", "learned") != "learned":
            raise ServingNotSupported(
                "rotary positions in a GPT-2 block are not served (the "
                "GPT-2 tree is served with learned positions; per-slot "
                "rotary offsets run in the latent-attention block)")
        if getattr(cfg, "moe_num_experts", 0):
            raise ServingNotSupported(
                "capacity-padded top-1/top-2 experts in a GPT-2 block are "
                "not served (moe/sharded_moe.py is the training layer; the "
                "served expert layer is moe/held_experts.py)")
        if getattr(cfg, "pp_stages", 1) != 1:
            raise ServingNotSupported(
                "pipeline-parallel serving is not supported (pp_stages "
                f"{cfg.pp_stages})")
        mode = str(getattr(cfg, "attention_mode", "auto"))
        if mode.startswith(("ring:", "ulysses:", "sparse")):
            raise ServingNotSupported(
                f"sequence-parallel and block-sparse attention are not "
                f"served (attention_mode={mode!r}): decode is dense "
                f"attention over the paged cache; serve with 'auto'")
        self.cfg = cfg
        self.cache = cache
        self.n_head = cfg.n_head
        self.head_dim = cfg.n_embd // cfg.n_head

    def embed(self, params, tok, pos):
        cfg = self.cfg
        # a pad or over-budget position can step past n_positions (its
        # row is discarded) and past the table: clamps keep both gathers
        # legal
        x = params["wte"][tok] + params["wpe"][
            jnp.minimum(pos, cfg.n_positions - 1)].astype(
                params["wte"].dtype)
        return x.reshape(-1, cfg.n_embd)

    def head(self, params, x):
        x = _ln(x, params["ln_f"])
        return jnp.einsum("be,ve->bv", x, params["wte"],
                          preferred_element_type=jnp.float32)

    def _requant(self, kv):
        """What the pool will hold for these rows: int8-round-tripped
        values, so the current token's self-attention matches what every
        later step reads (the flax decode path quantises on write too)."""
        if not self.cache.int8_kv:
            return kv
        kq, ks = quantize_kv(kv)
        return kq.astype(jnp.float32) * ks[..., None]

    def attend(self, layer, pools, bt, past_lens, C, q, k, v):
        """Rows ``[B*C, H, D]`` of one layer's q/k/v over each slot's PAST
        pages plus the chunk from registers; returns ``[B*C, H, D]``
        fp32 (:func:`_attend_in_lanes`)."""
        int8 = self.cache.int8_kv
        return _attend_in_lanes(
            layer * self.cache.num_blocks, pools, bt, past_lens, C, q,
            self._requant(k), self._requant(v),
            k_scale_pool=pools["k_scale"] if int8 else None,
            v_scale_pool=pools["v_scale"] if int8 else None)

    def block(self, layer, params, scales, x, pos, real, attend, pools,
              slot):
        """One transformer block over rows ``x [N, E]``: pre-LN
        attention (``attend(q, k, v)`` over ``[N, H, D]``) and the GELU
        MLP, each added to the residual. Returns the rows, the layer's
        ``k`` and ``v``, which the forward writes to the pools, no
        expert counts and the pools as they were."""
        p, s = params[f"h_{layer}"], _sub(scales, f"h_{layer}")
        N, E = x.shape
        H, D = self.n_head, self.head_dim
        qkv = _dense(_ln(x, p["ln_1"]), p["attn"]["qkv"],
                     _sub(s, "attn", "qkv"))
        q, k, v = (t.reshape(N, H, D) for t in jnp.split(qkv, 3, axis=-1))
        out = attend(q, k, v).reshape(N, E).astype(x.dtype)
        x = x + _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
        h = jax.nn.gelu(_dense(_ln(x, p["ln_2"]), p["mlp"]["fc"],
                               _sub(s, "mlp", "fc")), approximate=True)
        x = x + _dense(h, p["mlp"]["proj"], _sub(s, "mlp", "proj"))
        return x, {"k": k, "v": v}, None, pools


def _attend_in_lanes(first_block, pools, bt, past_lens, C, q, k, v,
                     **kw):
    """Heads in lanes over the ``k`` and ``v`` pools: q ``[B*C, H, D]``
    and k/v ``[B*C, K, D]`` (``K`` divides ``H``: grouped queries) of one
    layer over each slot's PAST pages plus the chunk from registers;
    returns ``[B*C, H, D]`` fp32. The shape decides what runs: a single
    query a slot is a decode step (the kernel where it can run), a chunk
    the jnp walk."""
    if C == 1:
        return paged_decode_attention(q, k, v, first_block, pools["k"],
                                      pools["v"], bt, past_lens, **kw)
    N, H, D = q.shape
    # a lone slot (a prefill chunk) goes without its batch dimension:
    # with it gpt2-medium's prefill program took 2.122 ms, without
    # it 2.050 (PERF.md, Findings)
    lead = () if N == C else (N // C,)

    def heads(t):                                       # -> [.., h, C, D]
        return jnp.moveaxis(t.reshape(lead + (C, t.shape[1], D)), -3, -2)

    out = paged_chunk_attention(
        heads(q), heads(k), heads(v), first_block, pools["k"], pools["v"],
        bt.reshape(lead + bt.shape[1:]), past_lens.reshape(lead), **kw)
    return jnp.moveaxis(out, -3, -2).reshape(N, H, D)


def _rms(x, gain, eps):
    """RMSNorm in float32: ``x * rsqrt(mean(x^2) + eps) * gain``."""
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _swiglu(x, p):
    return (jax.nn.silu(x @ p["gate"]) * (x @ p["up"])) @ p["down"]


def _mlp(x, p, kind):
    """A dense MLP of an expert kind (moe/held_experts.py): SwiGLU
    (``silu``) or ``relu(x U)^2 D`` (``relu2``)."""
    if kind == "silu":
        return _swiglu(x, p)
    return jnp.square(jax.nn.relu(x @ p["up"])) @ p["down"]


def _products(pos):
    """What an expert layer's grouped products are called on the device
    trace: ``expert_decode`` in a decode step (one token a slot), else
    ``expert_prefill``."""
    return "expert_decode" if pos.shape[-1] == 1 else "expert_prefill"


def _expert_layer(cfg, m, h, real, name):
    """The expert layer over rows ``h [N, E]`` (``real [N]``: the rows
    that are tokens, the only ones routed): the router over all of its
    experts, the held experts' part (one grouped product a weight, called
    ``name`` on the device trace) and the shared expert, summed in
    float32. Returns the rows and the counts."""
    chosen, weights = route(
        h, m["router"], m["router_bias"], k=cfg.num_experts_per_tok,
        n_group=cfg.n_group, topk_group=cfg.topk_group,
        scale=cfg.routed_scaling_factor)
    routed, counts = held_expert_mlp(
        h, chosen, weights, m["experts"], cfg.experts_held[0], real,
        cfg.mlp_hidden_act, name)
    y = _mlp(h, m["shared"], cfg.mlp_hidden_act).astype(jnp.float32) \
        + routed
    return y.astype(h.dtype), counts


class _MLAMoEBlocks:
    """The latent-attention block with experts (models/mla_moe.py) over
    its params pytree; one ``kv`` pool whose row is the token's latent.

    Every attention runs in the latent space, decode and chunk alike:
    the cache holds a token's normed latent ``c_kv`` and its rotated
    shared key ``k_pe`` and nothing else, so the key's up-projection is
    absorbed into the query (``q_lat = q_nope @ W_UK^T``), the scores are
    ``q_lat . c_kv + q_pe . k_pe``, the weighted sum is over ``c_kv`` and
    the value's up-projection comes after it (``o = o_lat @ W_UV``)."""

    def __init__(self, cfg, cache):
        from deepspeed_tpu.models.mla_moe import yarn_inv_freq
        self.cfg = cfg
        self.cache = cache
        self.inv_freq = yarn_inv_freq(cfg)

    def embed(self, params, tok, pos):
        return params["embed"][tok].reshape(-1, self.cfg.hidden_size)

    def head(self, params, x):
        x = _rms(x, params["norm_f"], self.cfg.rms_norm_eps)
        return jnp.einsum("be,ve->bv", x, params["head"],
                          preferred_element_type=jnp.float32)

    def _rope(self, x, pos):
        """Rotary positions at each row's own offset: ``x [N, ..., R]``,
        ``pos [N]``; the interleaved pair ``(2i, 2i+1)`` turns by
        ``pos * inv_freq[i]``, in float32."""
        angle = pos.astype(jnp.float32)[:, None] * self.inv_freq
        angle = angle.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (-1,))
        scale = self.cfg.rope_cos_sin_scale
        cos, sin = jnp.cos(angle) * scale, jnp.sin(angle) * scale
        pairs = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
        a, b = pairs[..., 0], pairs[..., 1]
        return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                         axis=-1).reshape(x.shape).astype(x.dtype)

    def attend(self, layer, pools, bt, past_lens, C, q, row):
        """``q [B*C, H, W']`` (absorbed) and the tokens' own rows
        ``[B*C, W']`` over each slot's past latents plus the chunk from
        registers; returns ``[B*C, H, kv_lora]`` fp32. One query a slot
        is a decode step (the kernel where it can run), a chunk the jnp
        walk, as for every served model."""
        kw = dict(sm_scale=self.cfg.softmax_scale,
                  v_width=self.cfg.kv_lora_rank)
        first_block = layer * self.cache.num_blocks
        if C == 1:
            return paged_decode_attention(q, row, None, first_block,
                                          pools["kv"], None, bt, past_lens,
                                          **kw)
        N, H, W = q.shape
        lead = () if N == C else (N // C,)      # a lone slot: no batch dim
        out = paged_chunk_attention(
            jnp.moveaxis(q.reshape(lead + (C, H, W)), -3, -2),
            row.reshape(lead + (C, W)), None, first_block, pools["kv"],
            None, bt.reshape(lead + bt.shape[1:]), past_lens.reshape(lead),
            **kw)
        return jnp.moveaxis(out, -3, -2).reshape(N, H, -1)

    def block(self, layer, params, scales, x, pos, real, attend, pools,
              slot):
        """One block over rows ``x [N, E]`` at positions ``pos`` (``N``
        of them): pre-norm latent attention, then the dense SwiGLU or the
        expert layer (shared expert + the held routed experts' part),
        each added to the residual. Returns the rows, the row each token
        caches, the expert layer's counts (None for a dense layer) and the
        pools as they were.
        ``real`` marks the rows that are tokens (the rest are a chunk's
        pad or a frozen slot): only they are routed."""
        cfg = self.cfg
        p = params[f"h_{layer}"]
        a = p["attn"]
        products = _products(pos)
        pos, real = pos.reshape(-1), real.reshape(-1)
        N, H = x.shape[0], cfg.num_attention_heads
        nope, lora = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        eps = cfg.rms_norm_eps
        h = _rms(x, p["norm_1"], eps)
        q = (_rms(h @ a["q_a"], a["q_norm"], eps) @ a["q_b"]).reshape(
            N, H, -1)
        ckv = h @ a["kv_a"]
        row = jnp.concatenate([_rms(ckv[:, :lora], a["kv_norm"], eps),
                               self._rope(ckv[:, lora:], pos)], axis=-1)
        q = jnp.concatenate(
            [jnp.einsum("nhd,chd->nhc", q[..., :nope], a["kv_b_k"]),
             self._rope(q[..., nope:], pos)], axis=-1)
        o = jnp.einsum("nhc,chd->nhd", attend(q, row).astype(x.dtype),
                       a["kv_b_v"]).reshape(N, -1)
        x = x + o @ a["o"]
        h = _rms(x, p["norm_2"], eps)
        if "moe" not in p:
            return x + _swiglu(h, p["mlp"]), {"kv": row}, None, pools
        y, counts = _expert_layer(cfg, p["moe"], h, real, products)
        return x + y, {"kv": row}, counts, pools


class _SSMHybridBlocks:
    """The state-space hybrid (models/ssm_hybrid.py) over its params
    pytree: K and V pools over the attention layers alone, and two pools
    a slot (the Mamba layers' float32 ``ssm`` state, packed as ops/ssm
    says, and ``conv``: the last ``d_conv - 1`` inputs of the convolution,
    in the activation dtype, which is theirs: exact).

    A Mamba layer reads its slots' state and writes it back where it lies,
    inside the layer loop: deferred like the K/V rows, every layer's new
    state would sit in temporaries at once (4.8 GB at 64 slots of
    granite-4.0-h-micro). A token at position 0 starts from a zero state,
    which serves a reused slot and a recomputed (preempted) request
    alike; a row that is no token (a chunk's pad tail, a frozen slot)
    leaves the state as it was. An expert layer holds no cache.

    ``B`` and ``C`` come in ``mamba_n_groups`` groups; a group's heads fill
    whole 128-lane rows of the packed state (two heads of 64 a row), so
    that each row of the decode kernel reads one group's."""

    def __init__(self, cfg, cache):
        group = cfg.mamba_n_heads // cfg.mamba_n_groups * cfg.mamba_d_head
        if cfg.mamba_n_groups > 1 and group % ssm.scan.LANES:
            raise ServingNotSupported(
                f"grouped B and C whose group's heads do not fill whole "
                f"{ssm.scan.LANES}-lane rows of the packed state "
                f"({cfg.mamba_n_groups} groups of {group} channels) are not "
                f"served: a row of the decode kernel reads one group's")
        self.cfg = cfg
        self.cache = cache
        self.kv_index = {l: i for i, l in enumerate(cfg.attention_layers)}
        self.state_index = {l: i for i, l in enumerate(cfg.mamba_layers)}

    def embed(self, params, tok, pos):
        x = params["embed"][tok].reshape(-1, self.cfg.hidden_size)
        return x * jnp.asarray(self.cfg.embedding_multiplier, x.dtype)

    def head(self, params, x):
        x = _rms(x, params["norm_f"], self.cfg.rms_norm_eps)
        table = params["embed" if self.cfg.tie_word_embeddings else "head"]
        return jnp.einsum("be,ve->bv", x, table,
                          preferred_element_type=jnp.float32) \
            / self.cfg.logits_scaling

    def attend(self, layer, pools, bt, past_lens, C, q, k, v):
        """Grouped queries over the pools' rows of this attention layer
        (:func:`_attend_in_lanes`), scores scaled by the configuration's
        multiplier; no position enters."""
        return _attend_in_lanes(
            self.kv_index[layer] * self.cache.num_blocks, pools, bt,
            past_lens, C, q, k, v, sm_scale=self.cfg.softmax_scale)

    def _mamba(self, m, p, h, pos, real, pools, slot):
        """The Mamba-2 mixer of rows ``h [B*C, E]``: ``C`` tokens of each
        of ``B`` slots (a decode step: every slot in order, ``C = 1``,
        ``slot`` None; prefill chunks through the chunked scan whatever
        their width: of slot ``slot`` at ``B = 1``, or of the slots
        ``slot [B]``, one a row, where a row with no real token is a pad
        row and moves no state), at positions ``pos [B, C]``, the real
        ones ``real [B, C]``. Returns its output rows and the pools with
        pool layer ``m`` of those slots' state moved past the real
        tokens."""
        cfg = self.cfg
        B, C = pos.shape
        Hm, P, N = cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state
        G, inner, W = cfg.mamba_n_groups, cfg.mamba_inner, cfg.conv_channels
        taps = cfg.mamba_d_conv - 1
        f32 = jnp.float32
        proj = h @ p["in_proj"]
        z, xbc, dt = (proj[:, :inner], proj[:, inner:inner + W],
                      proj[:, inner + W:])
        fresh = pos[:, 0] == 0                  # the first token: state 0
        if slot is None:                        # every slot, in order
            prev_conv = pools["conv"][m]
        elif slot.ndim:                         # a slot a row
            prev_conv = pools["conv"][m, slot]
        else:
            prev_conv = jax.lax.dynamic_index_in_dim(pools["conv"][m], slot,
                                                     keepdims=True)
        held = jnp.where(fresh[:, None], 0, prev_conv).reshape(B, taps, W)
        window = jnp.concatenate([held.astype(xbc.dtype),
                                  xbc.reshape(B, C, W)], axis=1)
        conv = p["conv_b"].astype(f32) + sum(
            window[:, k:k + C].astype(f32) * p["conv_w"][k].astype(f32)
            for k in range(taps + 1))
        conv = jax.nn.silu(conv)                        # [B, C, W]
        # the last ``taps`` inputs up to the last real token (a decode row
        # that moves on has one: a static slice, which keeps the pool's
        # layout where the per-slot slice of 384 slots had it relaid)
        n_real = real.sum(axis=1)
        tail = (window[:, 1:] if C == 1 else jax.vmap(
            lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, taps))(
                window, n_real)).reshape(B, taps * W)
        new_conv = jnp.where((n_real > 0)[:, None], tail.astype(
            prev_conv.dtype), prev_conv)
        live = real.astype(f32)[:, :, None]
        x = (conv[..., :inner] * live).reshape(B, C, Hm, P)
        b = conv[..., inner:inner + G * N].reshape(B, C, G, N)
        c = conv[..., inner + G * N:].reshape(B, C, G, N)
        step = jax.nn.softplus(dt.astype(f32).reshape(B, C, Hm)
                               + p["dt_bias"].astype(f32)) * live
        a = -jnp.exp(p["A_log"].astype(f32))
        if slot is None:                        # a decode step
            y, state = ssm.decode_update(
                pools["ssm"], m, x[:, 0], b[:, 0], c[:, 0], step[:, 0], a,
                real[:, 0], fresh)
            pools = dict(pools, ssm=state, conv=pools["conv"].at[m].set(
                new_conv))
            y = y[:, None]
        elif slot.ndim:                         # chunks, a slot a row
            rows = pools["ssm"][m, slot]
            s0 = jnp.where(fresh[:, None, None, None], 0.0,
                           ssm.to_heads(rows, Hm, P).astype(f32))
            y, s_end = jax.vmap(ssm.chunk_scan, (0, 0, 0, 0, None, 0))(
                x, b, c, step, a, s0)
            # a pad row's index lies past the slots: the scatters drop it
            to = jnp.where(n_real > 0, slot, pools["ssm"].shape[1])
            pools = dict(
                pools,
                ssm=pools["ssm"].at[m, to].set(
                    ssm.from_heads(s_end).astype(rows.dtype), mode="drop"),
                conv=pools["conv"].at[m, to].set(new_conv, mode="drop"))
        else:
            rows = pools["ssm"][m, slot]
            s0 = jnp.where(fresh[0], 0.0,
                           ssm.to_heads(rows, Hm, P).astype(f32))
            y, s_end = ssm.chunk_scan(x[0], b[0], c[0], step[0], a, s0)
            y = y[None]
            pools = dict(
                pools,
                ssm=pools["ssm"].at[m, slot].set(
                    ssm.from_heads(s_end).astype(rows.dtype)),
                conv=pools["conv"].at[m, slot].set(new_conv[0]))
        y = y + p["D"].astype(f32)[:, None] * x          # [B, C, Hm, P]
        y = y.reshape(B * C, inner) * jax.nn.silu(z.astype(f32))
        # the gated RMSNorm over each group's channels, one gain
        y = _rms(y.reshape(B * C, G, inner // G),
                 p["norm"].reshape(G, inner // G), cfg.rms_norm_eps)
        return y.reshape(B * C, inner).astype(h.dtype) @ p["out_proj"], \
            pools

    def block(self, layer, params, scales, x, pos, real, attend, pools,
              slot):
        """One layer over rows ``x [N, E]``: RMSNorm and the layer's part
        (grouped-query attention, the Mamba-2 mixer over the slots' state,
        or the expert layer), scaled onto the residual; then, where every
        mixer is followed by one, RMSNorm and the SwiGLU MLP likewise.
        Returns the rows, the K/V rows an attention layer caches (``None``
        for the others), the expert layer's counts (``None`` for the
        others), and the pools."""
        cfg = self.cfg
        p = params[f"h_{layer}"]
        N = x.shape[0]
        scale = jnp.asarray(cfg.residual_multiplier, x.dtype)
        h = _rms(x, p["norm_1"], cfg.rms_norm_eps)
        rows = counts = None
        if layer in self.state_index:
            mixed, pools = self._mamba(self.state_index[layer], p["mamba"],
                                       h, pos, real, pools, slot)
        elif "moe" in p:
            mixed, counts = _expert_layer(cfg, p["moe"], h, real.reshape(-1),
                                          _products(pos))
        else:
            a, D = p["attn"], cfg.head_dim
            q = (h @ a["q"]).reshape(N, -1, D)
            rows = {"k": (h @ a["k"]).reshape(N, -1, D),
                    "v": (h @ a["v"]).reshape(N, -1, D)}
            mixed = attend(q, rows["k"], rows["v"]).reshape(N, -1)
            mixed = mixed.astype(x.dtype) @ a["o"]
        x = x + scale * mixed
        if cfg.mlp_after_mixer:
            h = _rms(x, p["norm_2"], cfg.rms_norm_eps)
            gate_up = h @ p["mlp"]["w_in"]
            width = gate_up.shape[-1] // 2
            x = x + scale * ((jax.nn.silu(gate_up[:, :width])
                              * gate_up[:, width:]) @ p["mlp"]["w_out"])
        return x, rows, counts, pools


class PagedRunner:
    def __init__(self, model, cache, decode_steps=1):
        assert decode_steps >= 1
        self.decode_steps = int(decode_steps)
        cfg = model.config
        self.blocks = (_MLAMoEBlocks if serves_latent(cfg)
                       else _SSMHybridBlocks if serves_state(cfg)
                       else _GPT2Blocks)(cfg, cache)
        self.cfg = cfg
        self.cache = cache
        # the expert layers' counts of the dispatches since they were
        # last taken: int32 [4] device arrays (held_experts.py), which
        # the server lands with the step's tokens; stays empty for a
        # model without experts
        self.expert_counts = []
        # the pools are donated and the server re-threads the returned
        # ones, so the stale buffers are never touched. Donation alone
        # does not make the KV scatter an in-place update on the TPU: it
        # gives the compiler the alias, and the pools' row shape
        # (kv_cache.PagedKVCache) is what lets it write there without
        # converting the pool — the decode program's compile memory at
        # gpt2-medium, 40 slots: 4.74 GB of arguments + 0.04 GB of
        # temporaries, where the ``[L, N, H, BS, D]`` pool took 4.9 +
        # 8.1 GB (PERF.md, PR 27; tests/unit/test_serving_pool_layout.py
        # keeps the check)
        self._decode = jax.jit(self._decode_impl, donate_argnums=(2,))
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(2,))
        # copy-on-write block fork (prefix cache): ONE device block copy
        # across every pool leaf (all layers in one update apiece, on
        # the same folded rows the write scatters index). A third tiny
        # program — deliberately NOT part of decode/prefill, whose
        # signatures the one-program acceptance pins.
        self._copy_block = jax.jit(self._copy_block_impl,
                                   donate_argnums=(0,))

    # -------------------------------------------------------- block copy
    def _copy_block_impl(self, pools, src, dst):
        """Block ``src`` -> block ``dst`` in every layer (rows
        ``arange(L)*N + src`` -> ``+ dst``) of every paged leaf: every
        paged pool (K, V and the int8 scales, or the latent pool) shares
        the leading ``[L*N]`` block dim; a per-slot pool has no blocks.
        src/dst are traced int32 scalars, so every fork reuses one
        compiled program."""
        L = self.cache.n_layer
        src_rows = self.cache.layer_rows(src, n_layers=L)
        dst_rows = self.cache.layer_rows(dst, n_layers=L)
        paged = self.cache.pool_kinds()
        return {name: p.at[dst_rows].set(p[src_rows])
                if paged[name] == "paged" else p
                for name, p in pools.items()}

    def copy_block(self, pools, src, dst):
        """Fork one block's bytes: the COW path's single device op."""
        return self._copy_block(pools, jnp.int32(src), jnp.int32(dst))

    # ------------------------------------------------------- the forward
    def _forward(self, params, scales, pools, bt, past_lens, tok, pos, write,
                 n_layers=None, want_logits=True, slot=None):
        """The serving forward pass (:meth:`_layers`), its cache rows
        written in ONE scatter per pool, and the head. Returns ``(pools,
        logits [B*C, V], counts)``, the logits ``None`` unless wanted."""
        pools, x, counts, rows = self._layers(
            params, scales, pools, bt, past_lens, tok, pos, write, n_layers,
            slot)
        if rows is not None:
            pools = self.cache.write_layers(pools, *rows)
        if not want_logits:
            return pools, None, counts
        return pools, self.blocks.head(params, x), counts

    def _layers(self, params, scales, pools, bt, past_lens, tok, pos, write,
                n_layers=None, slot=None):
        """The serving forward pass: ``C`` tokens for each of ``B`` slots.

        tok/pos ``[B, C]``: the tokens and their absolute positions
        (``pos[b] = past_lens[b] + 0..C-1``); write ``[B, C]`` bool:
        which of them are real (a frozen slot, a chunk's pad tail and a
        candidate past a slot's budget are not: their cache rows go to
        the null block and their output rows are discarded by the
        caller); bt ``[B, MB]``; past_lens ``[B]``: tokens ALREADY in
        the pool; slot: the slots whose per-slot state prefill chunks
        move on, a traced scalar for one chunk (``B = 1``) or ``[B]``, one
        a row (``None``: the ``B`` rows are the slots, in order).

        Embeds, runs the first ``n_layers`` blocks (default: all) and
        returns ``(pools, x [B*C, E], counts, rows)``: the pools as the
        layers left them (moved on only where a layer carries per-slot
        state), the last layer's output rows, the expert layers' counts
        summed (``None`` for a model without experts), and what
        ``cache.write_layers`` takes to write those layers' cache rows:
        ``(new, block_ids, offsets)``, or ``None`` where no layer caches a
        row.

        ``n_layers < cfg.n_layer`` is the truncated-layer self-draft of
        serving/speculative.py: the SAME params pytree traced over a
        layer prefix (plus the shared ln_f and tied head) — zero extra
        weights, and the prefix layers' K/V are bit-identical to the
        target's, so draft writes land in the same pools."""
        blocks = self.blocks
        bs = self.cache.block_size
        B, C = tok.shape
        x = blocks.embed(params, tok, pos)
        new, counts = [], None
        for layer in range(self.cfg.n_layer if n_layers is None
                           else int(n_layers)):
            x, rows, n, pools = blocks.block(
                layer, params, scales, x, pos, write,
                functools.partial(blocks.attend, layer, pools, bt,
                                  past_lens, C), pools, slot)
            if rows is not None:        # a layer with per-slot state: none
                new.append(rows)
            if n is not None:
                counts = n if counts is None else counts + n
        if not new:
            return pools, x, counts, None
        row = jnp.take_along_axis(
            bt, jnp.minimum(pos // bs, bt.shape[1] - 1), axis=1)
        return pools, x, counts, (
            {name: jnp.stack([rows[name] for rows in new])
             for name in new[0]},
            jnp.where(write, row, 0).reshape(-1), (pos % bs).reshape(-1))

    # ---------------------------------------------------------- programs
    def _decode_impl(self, params, scales, pools, bt, pos, active, tok,
                     temp, top_p, lanes, budget, prev, prev_row):
        """``decode_steps`` iterations in one dispatch (lax.scan), each
        the forward at ``C = 1`` and a sampled token per slot.

        A slot's first input is ``tok`` (the host's) where ``prev_row`` is
        negative, else row ``prev_row`` of ``prev [K, B]``: the tokens
        the dispatch before this one returned, which the host has not
        read yet (the server runs a step ahead of the device; the very
        first dispatch passes zeros of the same shape, so there is one
        program).

        ``budget`` [B]: tokens this dispatch may produce per slot (the
        scheduler caps it by remaining generation / model length /
        allocated blocks). A slot past its budget FREEZES — its writes
        route to the null block, its position stops advancing, and its
        sampled tokens are discarded host-side. K=1 reduces to classic
        per-token continuous batching. Returns (pools, tokens [K, B],
        the expert layers' counts or None).
        """
        K = self.decode_steps
        tok = jnp.where(prev_row >= 0, jnp.take_along_axis(
            prev, jnp.maximum(prev_row, 0)[None], axis=0)[0], tok)

        def one(pools, step_pos, live, cur):
            pools, logits, counts = self._forward(
                params, scales, pools, bt, step_pos, cur[:, None],
                step_pos[:, None], live[:, None])
            return pools, sample_tokens(logits, temp, top_p, lanes,
                                        step_pos,
                                        vocab_size=self.cfg.vocab_size), \
                counts

        def body(carry, i):
            pools, cur = carry
            live = active & (i < budget)
            pools, nxt, counts = one(pools, pos + jnp.minimum(i, budget),
                                     live, cur)
            return (pools, jnp.where(live, nxt, cur)), (nxt, counts)

        if K == 1:
            pools, nxt, counts = one(pools, pos, active & (budget > 0), tok)
            return pools, nxt[None], counts
        (pools, _), (toks, counts) = jax.lax.scan(
            body, (pools, tok), jnp.arange(K, dtype=jnp.int32))
        return pools, toks, None if counts is None else counts.sum(0)

    @staticmethod
    def _chunk_rows(bt, tokens, start, n_valid):
        """The forward's ``bt, past_lens, tok, pos, write`` for prefill
        chunks: one a row (bt ``[R, MB]``, tokens ``[R, C]``, the rest
        ``[R]``), or one chunk without the row dimension (bt ``[MB]``,
        tokens ``[C]``, scalars), which the walk runs with no batch
        dimension (:func:`_attend_in_lanes`)."""
        if tokens.ndim == 1:
            idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
            return (bt[None], start[None], tokens[None],
                    (start + idx)[None], (idx < n_valid)[None])
        idx = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        return (bt, start, tokens, start[:, None] + idx,
                idx < n_valid[:, None])

    def _prefill_impl(self, params, scales, pools, bt, tokens, start,
                      n_valid, slot=None):
        """``R`` slots' chunks, one a row (:meth:`_chunk_rows`), no head:
        the positions past a row's ``n_valid`` are its chunk's pad;
        ``slot [R]`` names the slot whose per-slot state each row moves on
        (a model without one does not read it). Rows are filled in order,
        so a pad row (``n_valid`` 0, a table of zeros: it writes only to
        the null block and moves no state) follows the real ones.

        A call that holds one chunk runs it alone, in the lone-slot form:
        a row costs about a whole chunk wherever it is padding (on a v5e at
        gpt2-medium's widths 1.64 of a chunk's 2.05 ms; PERF.md,
        Findings). The conditional that runs the layers only computes the
        rows to cache (a pool written inside it is copied whole); the
        first chunk's rows are written after it, the other rows' only
        where the call holds more than one chunk, each write in place. So
        a model whose layers write their per-slot state as they go runs
        every row, and so does a call of one row. One chunk handed over
        without the row dimension is the lone-slot form and nothing
        else."""
        if tokens.ndim == 1 or len(tokens) == 1 or serves_state(self.cfg):
            pools, _, counts = self._forward(
                params, scales, pools, *self._chunk_rows(bt, tokens, start,
                                                         n_valid),
                want_logits=False, slot=slot)
            return pools, counts
        width = tokens.size

        def cached(*chunks):
            """The rows the chunks cache (padded to the call's width:
            the rest go to the null block) and the expert counts."""
            _, _, counts, (new, ids, offs) = self._layers(
                params, scales, pools, *self._chunk_rows(*chunks))
            pad = width - ids.shape[0]
            new = {name: jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (
                v.ndim - 2)) for name, v in new.items()}
            return new, jnp.pad(ids, (0, pad)), jnp.pad(offs, (0, pad)), \
                counts

        more = jnp.any(n_valid[1:] > 0)
        new, ids, offs, counts = jax.lax.cond(
            more, lambda: cached(bt, tokens, start, n_valid),
            lambda: cached(bt[0], tokens[0], start[0], n_valid[0]))

        def rows(lo, hi):
            return ({name: v[:, lo:hi] for name, v in new.items()},
                    ids[lo:hi], offs[lo:hi])
        C = tokens.shape[1]
        pools = self.cache.write_layers(pools, *rows(0, C))
        pools = jax.lax.cond(
            more, lambda p: self.cache.write_layers(p, *rows(C, width)),
            lambda p: p, pools)
        return pools, counts

    # -------------------------------------------------------- public API
    def decode_step(self, params, scales, pools, bt, pos, active, tok,
                    temp, top_p, lanes, budget, prev, prev_row):
        """One decode DISPATCH (``decode_steps`` tokens per slot, budget-
        capped); returns ``(pools, tokens [K, B] int32 device array)``.
        ``prev``/``prev_row``: the dispatch before's tokens and the row of
        them that is each slot's input (negative: ``tok``). The expert
        layers' counts, where the model has any, are kept for
        :meth:`take_expert_counts`."""
        pools, toks, counts = self._decode(
            params, scales or {}, pools, bt, pos, active, tok, temp, top_p,
            lanes, budget, prev, prev_row)
        if counts is not None:
            self.expert_counts.append(counts)
        return pools, toks

    def prefill_chunk(self, params, scales, pools, bt, tokens, start,
                      n_valid, slot=None):
        """Fill ``n_valid`` prompt tokens of each row's slot ``slot``'s KV
        (and move its per-slot state on), one chunk a row or, without the
        row dimension, one chunk (:meth:`_prefill_impl`); returns updated
        pools."""
        pools, counts = self._prefill(params, scales or {}, pools, bt,
                                      tokens, start, n_valid, slot)
        if counts is not None:
            self.expert_counts.append(counts)
        return pools

    def take_expert_counts(self) -> list:
        """The counts of the dispatches made since the last call, still
        on the device, each with its copy to the host under way."""
        taken, self.expert_counts = self.expert_counts, []
        return taken


# the name the GPT-2-only server gave its runner, which callers import
PagedGPT2Runner = PagedRunner
