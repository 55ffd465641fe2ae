"""What a decoder with latent attention and routed experts needs, from
shapes alone and whatever implements them (``configs/dots.vlm1.inst.json``
and its like; ``flops.py`` is GPT-2's count, with the chip's peaks).

A served token at position t (t earlier tokens in its context) needs, a
layer: twice the weights of its matrix products outside the routed experts
(the attention's five matrices; the dense MLP, or the router and the
shared expert), twice the EXPECTED share of routed experts held here
(``num_experts_per_tok`` x held / published of one expert: the router's
width is published, its choices fall on this chip's experts in that
share), and the EXPANDED attention's ``2 H (nope + rope + v) t`` (scores
and weighted sum against every earlier token's per-head key and value;
an absorbed implementation multiplies more and is charged the same).
Where a logit is needed, ``2 V E`` more for the untied head over the
rows held. No embedding lookup, no norms, no rotary.
"""


def _attention_weights(c) -> int:
    E, H = c["hidden_size"], c["num_attention_heads"]
    qk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    return (E * c["q_lora_rank"] + c["q_lora_rank"] * H * qk
            + E * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * H * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + H * c["v_head_dim"] * E)


def _expert_weights(c) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def weights_a_token(c) -> float:
    """Weights in the matrix products of one token through every layer as
    run, the routed experts at their expected share, the head left out."""
    E = c["hidden_size"]
    dense = c["first_k_dense_replace"]
    expert_layers = c["num_hidden_layers"] - dense
    share = (c["num_experts_per_tok"] * c["n_routed_experts"]
             / c["published"]["n_routed_experts"])
    return (c["num_hidden_layers"] * _attention_weights(c)
            + dense * 3 * E * c["intermediate_size"]
            + expert_layers * (E * c["published"]["n_routed_experts"]
                               + c["n_shared_experts"] * _expert_weights(c)
                               + share * _expert_weights(c)))


def attention_flops_a_context_token(c) -> float:
    """Expanded attention of one query against ONE earlier token, all
    layers: ``2 H (nope + rope + v)`` a layer."""
    return (2.0 * c["num_hidden_layers"] * c["num_attention_heads"]
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]))


def serve_flops(c, prompt_len: int, first: int, last: int) -> float:
    """Required operations to take one request from ``first`` output tokens
    delivered to ``last``, prompt of ``prompt_len`` (the convention of
    ``flops.serve_flops``: the last prompt token and every output token but
    the final one are the inputs that produce an output; the prompt is
    charged with the first output token)."""
    per_token, per_context = 2.0 * weights_a_token(c), \
        attention_flops_a_context_token(c)

    def span(a, b):                      # positions a .. b-1
        n = max(0, b - a)
        return n * per_token + per_context * (a + b - 1) * n / 2.0

    total = 2.0 * c["vocab_size"] * c["hidden_size"] * max(0, last - first)
    if last > first:
        lo = 0 if first == 0 else prompt_len + first - 1
        total += span(lo, prompt_len + last - 1)
    return total


def latent_decode_cost(c, blocks: int, block_size: int) -> dict:
    """Operations and HBM bytes of decode attention in the latent space
    over ``blocks`` cache blocks of ONE layer's walk summed over layers by
    the caller: a cached token is one row of ``kv_lora_rank +
    qk_rope_head_dim`` values, read once, unpadded, in the configuration's
    precision (2 B), which every head's query meets in full for the scores
    and in its first ``kv_lora_rank`` values for the weighted sum."""
    row = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    tokens = blocks * block_size
    return {"flops": float(tokens * 2 * c["num_attention_heads"]
                           * (row + c["kv_lora_rank"])),
            "bytes": float(tokens * row * 2)}
