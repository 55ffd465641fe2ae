"""Paged model runner — the one forward pass behind the server's programs.

The flax decode path (models/gpt2.py ``decode=True``) owns a per-batch
contiguous cache with ONE shared ``cache_index`` — every sequence in the
batch must sit at the same position, which is exactly what continuous
batching breaks. This runner re-expresses the same GPT-2 math directly
over the model's *params pytree* with per-slot positions and the paged
pool from serving/kv_cache.py, ONCE: :meth:`PagedGPT2Runner._forward`
embeds ``[B, C]`` tokens at their own positions, walks the blocks
(:meth:`PagedGPT2Runner._block`, the only definition of a transformer
block in the serving code), attends over each slot's past pages plus the
chunk itself, writes the K/V of every layer that ran in one scatter per
pool, and returns the logits. The four compiled programs are its callers:

* ``decode_step`` — the one static-shaped program the server calls every
  iteration: the forward at ``C = 1``, then a sampled token per request
  (serving/sampling.py), ``decode_steps`` times in one dispatch.
  Compiled once for the whole serving lifetime — request churn only
  changes tensor *values*.
* ``prefill_chunk`` — fills one slot's prompt KV ``chunk`` tokens at a
  time (serving/prefill.py plans the chunks) so a long prompt never
  stalls the decode batch: the forward at ``B = 1``, no head. Also
  compiled once: the final short chunk is padded and its tail writes are
  routed to the null block.
* the speculative draft and verify programs (serving/speculative.py):
  the forward at ``C = 1`` over a layer prefix, and at ``C = K+1``.

Which attention runs is read off the static shape, not an option: one
query a slot goes to ``paged_decode_attention`` (the Pallas kernel on a
TPU over bfloat16 pools under one device, else the jnp walk), a chunk to
the jnp walk (serving/paged_attention.py states the rule).

Weight formats: float kernels and the engine's TRUE int8 weight storage
(module_quantize ``quant_scales`` collection) both work — the dequant
folds into the matmul exactly like QuantDense. The int8 *KV* layout is
the cache's concern and composes transparently.

Scope guards (asserted at construction): GPT2LMHeadModel-family param
trees, learned position embeddings, no MoE / pipeline / sequence
parallelism, mp_size 1.
"""

import functools

import jax
import jax.numpy as jnp

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


class PagedGPT2Runner:
    def __init__(self, model, cache, decode_steps=1):
        assert decode_steps >= 1
        self.decode_steps = int(decode_steps)
        cfg = model.config
        for attr in ("n_layer", "n_head", "n_embd", "n_positions",
                     "vocab_size"):
            assert hasattr(cfg, attr), (
                f"serving needs a GPT2Config-like model config (missing "
                f"{attr!r}); got {type(cfg).__name__}")
        assert getattr(cfg, "position_embedding", "learned") == "learned", \
            "serving: rope per-slot offsets not wired yet; use 'learned'"
        assert getattr(cfg, "moe_num_experts", 0) == 0, \
            "serving: MoE decode not supported"
        assert getattr(cfg, "pp_stages", 1) == 1, \
            "serving: pipeline-parallel models not supported"
        mode = getattr(cfg, "attention_mode", "auto")
        assert not str(mode).startswith(("ring:", "ulysses:", "sparse")), (
            f"serving decode is dense KV-cache attention; "
            f"attention_mode={mode!r} models must serve with 'auto'")
        self.cfg = cfg
        self.cache = cache
        self.n_head = cfg.n_head
        self.head_dim = cfg.n_embd // cfg.n_head
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
        ``arange(L)*N + src`` -> ``+ dst``) of every leaf: K, V and the
        int8 scales share the leading ``[L*N]`` block dim. src/dst are
        traced int32 scalars, so every fork reuses one compiled
        program."""
        L = self.cache.n_layer
        src_rows = self.cache.layer_rows(src, n_layers=L)
        dst_rows = self.cache.layer_rows(dst, n_layers=L)
        return {name: p.at[dst_rows].set(p[src_rows])
                for name, p in pools.items()}

    def copy_block(self, pools, src, dst):
        """Fork one block's bytes: the COW path's single device op."""
        return self._copy_block(pools, jnp.int32(src), jnp.int32(dst))

    # ------------------------------------------------------- the forward
    def _requant(self, kv):
        """What the pool will hold for these rows: int8-round-tripped
        values, so the current token's self-attention matches what every
        later step reads (the flax decode path quantises on write too)."""
        if not self.cache.int8_kv:
            return kv
        kq, ks = quantize_kv(kv)
        return kq.astype(jnp.float32) * ks[..., None]

    def _attend(self, layer, pools, bt, past_lens, C, q, k, v):
        """Rows ``[B*C, H, D]`` of one layer's q/k/v over each slot's PAST
        pages plus the chunk from registers; returns ``[B*C, H, D]``
        fp32. The shape decides what runs: a single query a slot is a
        decode step (the kernel where it can run), a chunk the jnp
        walk."""
        int8 = self.cache.int8_kv
        first_block = layer * self.cache.num_blocks
        scale_pools = dict(
            k_scale_pool=pools["k_scale"] if int8 else None,
            v_scale_pool=pools["v_scale"] if int8 else None)
        k, v = self._requant(k), self._requant(v)
        if C == 1:
            return paged_decode_attention(q, k, v, first_block, pools["k"],
                                          pools["v"], bt, past_lens,
                                          **scale_pools)
        N, H, D = q.shape
        # a lone slot (a prefill chunk) goes without its batch dimension:
        # with it gpt2-medium's prefill program took 2.122 ms, without
        # it 2.050 (PERF.md, PR 32)
        lead = () if N == C else (N // C,)

        def heads(t):                                   # -> [.., H, C, D]
            return jnp.moveaxis(t.reshape(lead + (C, H, D)), -3, -2)

        out = paged_chunk_attention(
            heads(q), heads(k), heads(v), first_block, pools["k"],
            pools["v"], bt.reshape(lead + bt.shape[1:]),
            past_lens.reshape(lead), **scale_pools)
        return jnp.moveaxis(out, -3, -2).reshape(N, H, D)

    def _block(self, p, s, x, attend):
        """One transformer block over rows ``x [N, E]``: pre-LN
        attention (``attend(q, k, v)`` over ``[N, H, D]``) and the GELU
        MLP, each added to the residual. Returns the rows and the
        layer's ``k`` and ``v``, which the forward writes to the pools."""
        N, E = x.shape
        H, D = self.n_head, self.head_dim
        qkv = _dense(_ln(x, p["ln_1"]), p["attn"]["qkv"],
                     _sub(s, "attn", "qkv"))
        q, k, v = (t.reshape(N, H, D) for t in jnp.split(qkv, 3, axis=-1))
        out = attend(q, k, v).reshape(N, E).astype(x.dtype)
        x = x + _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
        h = jax.nn.gelu(_dense(_ln(x, p["ln_2"]), p["mlp"]["fc"],
                               _sub(s, "mlp", "fc")), approximate=True)
        return x + _dense(h, p["mlp"]["proj"], _sub(s, "mlp", "proj")), k, v

    def _forward(self, params, scales, pools, bt, past_lens, tok, pos, write,
                 n_layers=None, want_logits=True):
        """The serving forward pass: ``C`` tokens for each of ``B`` slots.

        tok/pos ``[B, C]``: the tokens and their absolute positions
        (``pos[b] = past_lens[b] + 0..C-1``); write ``[B, C]`` bool:
        which of them are real (a frozen slot, a chunk's pad tail and a
        candidate past a slot's budget are not: their K/V go to the null
        block and their output rows are discarded by the caller); bt
        ``[B, MB]``; past_lens ``[B]``: tokens ALREADY in the pool.

        Embeds, runs the first ``n_layers`` blocks (default: all),
        writes those layers' K/V in ONE scatter per pool, and returns
        ``(pools, logits [B*C, V])``, the logits ``None`` unless wanted.

        ``n_layers < cfg.n_layer`` is the truncated-layer self-draft of
        serving/speculative.py: the SAME params pytree traced over a
        layer prefix (plus the shared ln_f and tied head) — zero extra
        weights, and the prefix layers' K/V are bit-identical to the
        target's, so draft writes land in the same pools."""
        cfg = self.cfg
        bs = self.cache.block_size
        B, C = tok.shape
        # a pad or over-budget position can step past n_positions (its
        # row is discarded) and past the table: clamps keep both gathers
        # legal
        x = params["wte"][tok] + params["wpe"][
            jnp.minimum(pos, cfg.n_positions - 1)].astype(
                params["wte"].dtype)
        x = x.reshape(B * C, cfg.n_embd)
        ks, vs = [], []
        for layer in range(cfg.n_layer if n_layers is None
                           else int(n_layers)):
            x, k, v = self._block(
                params[f"h_{layer}"], _sub(scales, f"h_{layer}"), x,
                functools.partial(self._attend, layer, pools, bt,
                                  past_lens, C))
            ks.append(k)
            vs.append(v)
        row = jnp.take_along_axis(
            bt, jnp.minimum(pos // bs, bt.shape[1] - 1), axis=1)
        pools = self.cache.write_layers(
            pools, jnp.stack(ks), jnp.stack(vs),
            jnp.where(write, row, 0).reshape(-1), (pos % bs).reshape(-1))
        if not want_logits:
            return pools, None
        x = _ln(x, params["ln_f"])
        return pools, jnp.einsum("be,ve->bv", x, params["wte"],
                                 preferred_element_type=jnp.float32)

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
        per-token continuous batching. Returns (pools, tokens [K, B]).
        """
        K = self.decode_steps
        tok = jnp.where(prev_row >= 0, jnp.take_along_axis(
            prev, jnp.maximum(prev_row, 0)[None], axis=0)[0], tok)

        def one(pools, step_pos, live, cur):
            pools, logits = self._forward(
                params, scales, pools, bt, step_pos, cur[:, None],
                step_pos[:, None], live[:, None])
            return pools, sample_tokens(logits, temp, top_p, lanes,
                                        step_pos,
                                        vocab_size=self.cfg.vocab_size)

        def body(carry, i):
            pools, cur = carry
            live = active & (i < budget)
            pools, nxt = one(pools, pos + jnp.minimum(i, budget), live, cur)
            return (pools, jnp.where(live, nxt, cur)), nxt

        if K == 1:
            pools, nxt = one(pools, pos, active & (budget > 0), tok)
            return pools, nxt[None]
        (pools, _), toks = jax.lax.scan(
            body, (pools, tok), jnp.arange(K, dtype=jnp.int32))
        return pools, toks

    def _prefill_impl(self, params, scales, pools, bt_row, tokens, start,
                      n_valid):
        """One slot's chunk: the forward at ``B = 1``, no head; the
        positions past ``n_valid`` are the final chunk's pad."""
        idx = jnp.arange(tokens.shape[0], dtype=jnp.int32)
        pools, _ = self._forward(
            params, scales, pools, bt_row[None], start[None], tokens[None],
            (start + idx)[None], (idx < n_valid)[None], want_logits=False)
        return pools

    # -------------------------------------------------------- public API
    def decode_step(self, params, scales, pools, bt, pos, active, tok,
                    temp, top_p, lanes, budget, prev, prev_row):
        """One decode DISPATCH (``decode_steps`` tokens per slot, budget-
        capped); returns ``(pools, tokens [K, B] int32 device array)``.
        ``prev``/``prev_row``: the dispatch before's tokens and the row of
        them that is each slot's input (negative: ``tok``)."""
        return self._decode(params, scales or {}, pools, bt, pos, active,
                            tok, temp, top_p, lanes, budget, prev, prev_row)

    def prefill_chunk(self, params, scales, pools, bt_row, tokens, start,
                      n_valid):
        """Fill ``n_valid`` prompt tokens of one slot's KV; returns
        updated pools."""
        return self._prefill(params, scales or {}, pools, bt_row, tokens,
                             start, n_valid)
