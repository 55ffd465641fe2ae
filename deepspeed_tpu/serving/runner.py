"""Paged model runner — the two compiled programs behind the server.

The flax decode path (models/gpt2.py ``decode=True``) owns a per-batch
contiguous cache with ONE shared ``cache_index`` — every sequence in the
batch must sit at the same position, which is exactly what continuous
batching breaks. This runner re-expresses the same GPT-2 math directly
over the model's *params pytree* with per-slot positions and the paged
pool from serving/kv_cache.py:

* ``decode_step`` — the one static-shaped program the server calls every
  iteration: embeds each slot's last token at its own position, writes
  its K/V through the slot's block table, gathers pages into the
  contiguous view ``decode_attention`` reads (per-sequence lengths), and
  samples the next token per request (serving/sampling.py). Compiled
  once for the whole serving lifetime — request churn only changes
  tensor *values*.
* ``prefill_chunk`` — fills one slot's prompt KV ``chunk`` tokens at a
  time (serving/prefill.py plans the chunks) so a long prompt never
  stalls the decode batch. Also compiled once: the final short chunk is
  padded and its tail writes are routed to the null block.

Weight formats: float kernels and the engine's TRUE int8 weight storage
(module_quantize ``quant_scales`` collection) both work — the dequant
folds into the matmul exactly like QuantDense. The int8 *KV* layout is
the cache's concern and composes transparently.

Scope guards (asserted at construction): GPT2LMHeadModel-family param
trees, learned position embeddings, no MoE / pipeline / sequence
parallelism, mp_size 1.
"""

import jax
import jax.numpy as jnp

from deepspeed_tpu.ops.quantizer.int8_linear import int8_matmul
from deepspeed_tpu.ops.transformer.decode import (decode_attention,
                                                  decode_attention_quantized,
                                                  quantize_kv)
from deepspeed_tpu.serving.paged_attention import (paged_decode_attention,
                                                   paged_prefill_attention)
from deepspeed_tpu.serving.sampling import NEG_INF, sample_tokens

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
    def __init__(self, model, cache, use_flash=None,
                 attention_impl="paged", decode_steps=1):
        """``attention_impl``: ``"paged"`` (default) streams attention
        over LIVE KV blocks — per-step traffic scales with how many
        tokens actually exist (serving/paged_attention.py: a Pallas
        kernel over each slot's own blocks for decode on a TPU, a
        dynamic-trip-count loop elsewhere). ``"gather"`` materialises each
        slot's pages into the contiguous view the
        ops/transformer/decode.py Pallas kernel reads — fixed
        ``T_max``-window traffic, but the decode GEMMs run in the tuned
        TPU kernel."""
        assert attention_impl in ("paged", "gather"), attention_impl
        assert decode_steps >= 1
        self.attention_impl = attention_impl
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
        self.use_flash = use_flash
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

    # ------------------------------------------------------------ layers
    def _qkv(self, p, s, x):
        B_or_C = x.shape[0]
        H, D = self.n_head, self.head_dim
        qkv = _dense(_ln(x, p["ln_1"]), p["attn"]["qkv"],
                     _sub(s, "attn", "qkv"))
        q, k, v = jnp.split(qkv, 3, axis=-1)
        return (q.reshape(B_or_C, H, D), k.reshape(B_or_C, H, D),
                v.reshape(B_or_C, H, D))

    def _requant(self, kv):
        """What the pool will hold for these rows: int8-round-tripped
        values, so the current token's self-attention matches what every
        later step reads (the flax decode path quantises on write too)."""
        if not self.cache.int8_kv:
            return kv
        kq, ks = quantize_kv(kv)
        return kq.astype(jnp.float32) * ks[..., None]

    def _attn_decode(self, p, s, layer, x, pools, bt, pos, active):
        """Paged impl: attend over PAST pool + current token from
        registers; returns the layer's (k, v) so the caller scatters all
        layers at once. Gather impl: eager per-layer write, then the
        ops/transformer/decode.py kernel over the contiguous view."""
        B, E = x.shape
        int8 = self.cache.int8_kv
        q, k, v = self._qkv(p, s, x)
        if self.attention_impl == "paged":
            out = paged_decode_attention(
                q, self._requant(k), self._requant(v),
                layer * self.cache.num_blocks, pools["k"], pools["v"],
                bt, pos,
                k_scale_pool=pools["k_scale"] if int8 else None,
                v_scale_pool=pools["v_scale"] if int8 else None)
            out = out.reshape(B, E).astype(x.dtype)
            proj = _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
            return pools, proj, (k, v)
        bs = self.cache.block_size
        row = jnp.take_along_axis(bt, (pos // bs)[:, None], axis=1)[:, 0]
        blk = jnp.where(active, row, 0)
        pools = self.cache.write_layers(pools, k[None], v[None], blk,
                                        pos % bs, first_layer=layer)
        lens = pos + 1
        kg, vg, ksg, vsg = self.cache.gather(pools, layer, bt)
        q4 = q[:, :, None, :]
        if int8:
            out = decode_attention_quantized(
                q4, kg, ksg, vg, vsg, lens, use_flash=self.use_flash)
        else:
            out = decode_attention(q4, kg, vg, lens,
                                   use_flash=self.use_flash)
        out = out[:, :, 0, :].reshape(B, E).astype(x.dtype)
        proj = _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
        return pools, proj, None

    def _attn_prefill(self, p, s, layer, x, pools, bt_row, pos, start,
                      n_valid):
        """Chunk attention for one slot. Paged impl: past pages + the
        chunk from registers (write deferred to one stacked scatter).
        Gather impl: eager write, dense masked attention over the
        contiguous view."""
        C, E = x.shape
        D = self.head_dim
        int8 = self.cache.int8_kv
        q, k, v = self._qkv(p, s, x)                    # [C, H, D]
        qh = q.transpose(1, 0, 2)                       # [H, C, D]
        if self.attention_impl == "paged":
            out = paged_prefill_attention(
                qh, self._requant(k).transpose(1, 0, 2),
                self._requant(v).transpose(1, 0, 2),
                layer * self.cache.num_blocks, pools["k"], pools["v"],
                bt_row, pos, start,
                k_scale_pool=pools["k_scale"] if int8 else None,
                v_scale_pool=pools["v_scale"] if int8 else None)
            out = out.transpose(1, 0, 2).reshape(C, E).astype(x.dtype)
            proj = _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
            return pools, proj, (k, v)
        bs = self.cache.block_size
        MB = bt_row.shape[0]
        valid = jnp.arange(C) < n_valid
        blk = jnp.where(valid,
                        bt_row[jnp.minimum(pos // bs, MB - 1)], 0)
        pools = self.cache.write_layers(pools, k[None], v[None], blk,
                                        pos % bs, first_layer=layer)
        kg, vg, ksg, vsg = self.cache.gather(pools, layer, bt_row)
        if int8:
            kg = (kg.astype(jnp.float32) * ksg[..., None]).astype(x.dtype)
            vg = (vg.astype(jnp.float32) * vsg[..., None]).astype(x.dtype)
        scores = jnp.einsum("hcd,htd->hct", qh, kg.astype(qh.dtype),
                            preferred_element_type=jnp.float32)
        scores = scores * (D ** -0.5)
        T = kg.shape[1]
        mask = jnp.arange(T)[None, :] <= pos[:, None]   # [C, T]
        scores = jnp.where(mask[None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("hct,htd->hcd", probs.astype(vg.dtype), vg)
        out = out.transpose(1, 0, 2).reshape(C, E).astype(x.dtype)
        proj = _dense(out, p["attn"]["proj"], _sub(s, "attn", "proj"))
        return pools, proj, None

    def _mlp(self, p, s, x):
        h = jax.nn.gelu(_dense(_ln(x, p["ln_2"]), p["mlp"]["fc"],
                               _sub(s, "mlp", "fc")), approximate=True)
        return _dense(h, p["mlp"]["proj"], _sub(s, "mlp", "proj"))

    # ---------------------------------------------------------- programs
    def _stack_decode(self, params, scales, pools, bt, pos, live, tok,
                      n_layers=None):
        """Embed each live slot's token at its own position, run the
        first ``n_layers`` of the stack (default: all), write those
        layers' K/V, and return ``(pools, logits)``.

        ``n_layers < cfg.n_layer`` is the truncated-layer self-draft of
        serving/speculative.py: the SAME params pytree traced over a
        layer prefix (plus the shared ln_f and tied head) — zero extra
        weights, and the prefix layers' K/V are bit-identical to the
        target's, so draft writes land in the same pools."""
        cfg = self.cfg
        bs = self.cache.block_size
        L = cfg.n_layer if n_layers is None else int(n_layers)
        x = params["wte"][tok] + params["wpe"][pos].astype(
            params["wte"].dtype)
        kv_stack = []
        for layer in range(L):
            p = params[f"h_{layer}"]
            s = _sub(scales, f"h_{layer}")
            pools, a, kv = self._attn_decode(p, s, layer, x, pools, bt,
                                             pos, live)
            if kv is not None:
                kv_stack.append(kv)
            x = x + a
            x = x + self._mlp(p, s, x)
        if kv_stack:
            # paged impl: ONE scatter per pool for the layers that ran;
            # non-live slots land in the null block
            row = jnp.take_along_axis(bt, (pos // bs)[:, None],
                                      axis=1)[:, 0]
            blk = jnp.where(live, row, 0)
            pools = self.cache.write_layers(
                pools, jnp.stack([k for k, _ in kv_stack]),
                jnp.stack([v for _, v in kv_stack]), blk, pos % bs)
        x = _ln(x, params["ln_f"])
        logits = jnp.einsum("be,ve->bv", x, params["wte"],
                            preferred_element_type=jnp.float32)
        return pools, logits

    def _decode_one(self, params, scales, pools, bt, pos, live, tok,
                    temp, top_p, lanes):
        """One decode iteration over the slot batch: embed each live
        slot's token at its own position, run the stack, write all
        layers' K/V, sample."""
        pools, logits = self._stack_decode(params, scales, pools, bt,
                                           pos, live, tok)
        nxt = sample_tokens(logits, temp, top_p, lanes, pos,
                            vocab_size=self.cfg.vocab_size)
        return pools, nxt

    def _decode_impl(self, params, scales, pools, bt, pos, active, tok,
                     temp, top_p, lanes, budget):
        """``decode_steps`` iterations in one dispatch (lax.scan).

        ``budget`` [B]: tokens this dispatch may produce per slot (the
        scheduler caps it by remaining generation / model length /
        allocated blocks). A slot past its budget FREEZES — its writes
        route to the null block, its position stops advancing, and its
        sampled tokens are discarded host-side. K=1 reduces to classic
        per-token continuous batching. Returns (pools, tokens [K, B]).
        """
        K = self.decode_steps

        def body(carry, i):
            pools, cur = carry
            step_pos = pos + jnp.minimum(i, budget)
            live = active & (i < budget)
            pools, nxt = self._decode_one(params, scales, pools, bt,
                                          step_pos, live, cur, temp,
                                          top_p, lanes)
            cur = jnp.where(live, nxt, cur)
            return (pools, cur), nxt

        if K == 1:
            live = active & (budget > 0)
            pools, nxt = self._decode_one(params, scales, pools, bt, pos,
                                          live, tok, temp, top_p, lanes)
            return pools, nxt[None]
        (pools, _), toks = jax.lax.scan(
            body, (pools, tok), jnp.arange(K, dtype=jnp.int32))
        return pools, toks

    def _prefill_impl(self, params, scales, pools, bt_row, tokens, start,
                      n_valid):
        cfg = self.cfg
        bs = self.cache.block_size
        MB = bt_row.shape[0]
        C = tokens.shape[0]
        pos = start + jnp.arange(C, dtype=jnp.int32)
        # the padded tail of the final chunk can step past n_positions;
        # its embedding rows are discarded, clamp keeps the gather legal
        pos_emb = jnp.minimum(pos, cfg.n_positions - 1)
        x = params["wte"][tokens] + params["wpe"][pos_emb].astype(
            params["wte"].dtype)
        kv_stack = []
        for layer in range(cfg.n_layer):
            p = params[f"h_{layer}"]
            s = _sub(scales, f"h_{layer}")
            pools, a, kv = self._attn_prefill(p, s, layer, x, pools,
                                              bt_row, pos, start, n_valid)
            if kv is not None:
                kv_stack.append(kv)
            x = x + a
            x = x + self._mlp(p, s, x)
        if kv_stack:
            valid = jnp.arange(C) < n_valid
            blk = jnp.where(valid,
                            bt_row[jnp.minimum(pos // bs, MB - 1)], 0)
            pools = self.cache.write_layers(
                pools, jnp.stack([k for k, _ in kv_stack]),
                jnp.stack([v for _, v in kv_stack]), blk, pos % bs)
        return pools

    # -------------------------------------------------------- public API
    def decode_step(self, params, scales, pools, bt, pos, active, tok,
                    temp, top_p, lanes, budget):
        """One decode DISPATCH (``decode_steps`` tokens per slot, budget-
        capped); returns ``(pools, tokens [K, B] int32 device array)``."""
        return self._decode(params, scales or {}, pools, bt, pos, active,
                            tok, temp, top_p, lanes, budget)

    def prefill_chunk(self, params, scales, pools, bt_row, tokens, start,
                      n_valid):
        """Fill ``n_valid`` prompt tokens of one slot's KV; returns
        updated pools."""
        return self._prefill(params, scales or {}, pools, bt_row, tokens,
                             start, n_valid)
