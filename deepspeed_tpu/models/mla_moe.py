"""A decoder with latent attention (MLA) and sigmoid group-limited experts.

The family of DeepSeek-V3-shaped language models (the language model of
``dots.vlm1.inst`` is the one the benchmark runs): RMSNorm, multi-head
LATENT attention (low-rank queries; keys and values expanded from one
``kv_lora_rank``-wide latent a token, a single rotary key shared by the
heads, YaRN-scaled frequencies), SwiGLU MLPs, ``first_k_dense_replace``
leading dense layers and then expert layers: a sigmoid router over
``n_routed_experts`` outputs with a selection bias, group-limited top-k,
the chosen scores normalised and scaled, one shared expert beside them.

This file holds the configuration and the parameter tree, nothing that
runs: the SERVING forward over them is serving/runner.py's (the one
``_forward`` every served model goes through), the expert layer
moe/held_experts.py's. There is no training forward, no loss and no
multi-token-prediction module here (ROADMAP B says what stays).

A chip may hold a SHARE of a layer: ``experts_held = (first, stop)`` names
the routed experts whose weights are here (the router keeps its published
width and routes over all of them), and ``vocab_size`` is the slice of
the vocabulary held. The share's partial result is what goes on: nothing
stands in for the absent chips.

Parameter tree (no bias anywhere; ``E`` hidden, ``H`` heads)::

    embed [V, E]          head [V, E] (untied)        norm_f [E]
    h_<i>/norm_1, norm_2 [E]
    h_<i>/attn: q_a [E, q_lora]      q_norm [q_lora]
                q_b [q_lora, H*(nope+rope)]   (per head: nope ‖ rope)
                kv_a [E, kv_lora+rope]        kv_norm [kv_lora]
                kv_b_k [kv_lora, H, nope]     kv_b_v [kv_lora, H, v]
                o [H*v, E]
    h_<i>/mlp (dense layers): gate, up [E, I]   down [I, E]
    h_<i>/moe (expert layers): router [E, n_routed]   router_bias [n_routed]
                shared: gate, up [E, n_shared*M]   down [n_shared*M, E]
                experts: gate_up [held, E, 2*M]  (gate ‖ up)   down [held, M, E]

``kv_b_k`` and ``kv_b_v`` are the two halves of the published ``kv_b``
matrix (per head ``nope`` key columns, then ``v`` value columns), kept
apart because the absorbed decode multiplies by each alone; ``gate_up``
is an expert's gate and up matrices side by side, one grouped product
for both.
"""

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class MLAMoEConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    first_k_dense_replace: int
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int               # the router's width
    experts_held: Tuple[int, int]       # [first, stop) of them held here
    num_experts_per_tok: int
    n_group: int
    topk_group: int
    routed_scaling_factor: float
    num_attention_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    max_position_embeddings: int
    n_shared_experts: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0            # YaRN; 1.0 = plain rotary
    rope_original_max_position: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0

    # the experts' kind (moe/held_experts.py): SwiGLU, as in every file
    mlp_hidden_act = "silu"

    def __post_init__(self):
        first, stop = self.experts_held
        if not 0 <= first < stop <= self.n_routed_experts:
            raise ValueError(f"experts_held {self.experts_held} is no range "
                             f"of the {self.n_routed_experts} routed experts")
        if self.n_routed_experts % self.n_group:
            raise ValueError("n_routed_experts must divide into n_group")

    # the names the server reads of every model
    @property
    def n_layer(self) -> int:
        return self.num_hidden_layers

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def latent_width(self) -> int:
        """Values cached a token and layer: the normed latent and the
        rotated shared key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, layer: int) -> bool:
        return layer >= self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        """``(nope + rope)^-1/2 * m^2`` with YaRN's
        ``m = 0.1 * mscale_all_dim * ln(factor) + 1``."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_factor > 1.0 and self.rope_mscale_all_dim:
            m = 0.1 * self.rope_mscale_all_dim * math.log(self.rope_factor) + 1
            scale *= m * m
        return scale

    @property
    def rope_cos_sin_scale(self) -> float:
        """YaRN's factor on cos and sin: ``mscale / mscale_all_dim`` of the
        same ``m`` form (1 where the two are equal)."""
        if self.rope_factor <= 1.0:
            return 1.0

        def m(s):
            return 0.1 * s * math.log(self.rope_factor) + 1.0 if s else 1.0
        return m(self.rope_mscale) / m(self.rope_mscale_all_dim)


def yarn_inv_freq(cfg: MLAMoEConfig) -> np.ndarray:
    """The rotary frequencies ``[rope/2]`` float32: plain
    ``theta^(-2i/d)`` below the correction range, divided by ``factor``
    above it, a linear ramp between (YaRN, Peng et al. 2023, as the
    published modelling code computes it)."""
    d = cfg.qk_rope_head_dim
    f = cfg.rope_theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if cfg.rope_factor <= 1.0:
        return f.astype(np.float32)

    def correction_dim(rotations):
        return (d * math.log(cfg.rope_original_max_position
                             / (rotations * 2 * math.pi))
                / (2 * math.log(cfg.rope_theta)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / max(high - low, 1e-3), 0.0, 1.0)
    return (f * (1.0 - ramp) + (f / cfg.rope_factor) * ramp).astype(
        np.float32)


class MLAMoEForCausalLM:
    """The model object ``init_inference`` is handed: its configuration.
    The forward that runs is the server's (module docstring)."""

    def __init__(self, config: MLAMoEConfig):
        self.config = config

    def apply(self, *args, **kwargs):
        raise NotImplementedError(
            "MLAMoEForCausalLM has no full-sequence forward: it is served "
            "through init_serving (serving/runner.py); the training forward "
            "is ROADMAP B's")


def init_params(cfg: MLAMoEConfig, key, dtype=jnp.float32, std=0.02):
    """A seeded parameter tree in the layout of the module docstring:
    matrices N(0, ``std``), norm gains and nothing else at 1, the router's
    selection bias at 0 (what a checkpoint would overwrite)."""
    E, H = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    M, held = cfg.moe_intermediate_size, cfg.n_held
    counter = iter(range(1 << 30))

    def w(*shape):
        return (std * jax.random.normal(jax.random.fold_in(key, next(counter)),
                                        shape, jnp.float32)).astype(dtype)

    def ones(n):
        return jnp.ones((n,), dtype)

    def mlp(width):
        return {"gate": w(E, width), "up": w(E, width), "down": w(width, E)}

    tree = {"embed": w(cfg.vocab_size, E), "head": w(cfg.vocab_size, E),
            "norm_f": ones(E)}
    for i in range(cfg.num_hidden_layers):
        layer = {
            "norm_1": ones(E), "norm_2": ones(E),
            "attn": {"q_a": w(E, cfg.q_lora_rank),
                     "q_norm": ones(cfg.q_lora_rank),
                     "q_b": w(cfg.q_lora_rank, H * (nope + rope)),
                     "kv_a": w(E, cfg.kv_lora_rank + rope),
                     "kv_norm": ones(cfg.kv_lora_rank),
                     "kv_b_k": w(cfg.kv_lora_rank, H, nope),
                     "kv_b_v": w(cfg.kv_lora_rank, H, v),
                     "o": w(H * v, E)}}
        if cfg.is_expert_layer(i):
            layer["moe"] = {
                "router": w(E, cfg.n_routed_experts),
                "router_bias": jnp.zeros((cfg.n_routed_experts,), dtype),
                "shared": mlp(cfg.n_shared_experts * M),
                "experts": {"gate_up": w(held, E, 2 * M),
                            "down": w(held, M, E)}}
        else:
            layer["mlp"] = mlp(cfg.intermediate_size)
        tree[f"h_{i}"] = layer
    return tree
