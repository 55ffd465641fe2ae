"""A hybrid decoder: Mamba-2 state-space layers beside grouped-query
attention layers without positions, and (in one of the two families)
layers of routed experts.

``layer_types`` names each layer ``mamba``, ``attention`` or ``moe``. Two
published families run through it, told apart by what their
configurations declare:

* ``granitemoehybrid`` with no experts (``granite-4.0-h-micro``):
  ``mlp_after_mixer``: every layer is RMSNorm, its mixer, RMSNorm, a
  SwiGLU MLP, each scaled by ``residual_multiplier`` onto the residual;
  the embedding is scaled by ``embedding_multiplier``, the tied head's
  logits divided by ``logits_scaling``.
* ``nemotron_h`` (``NVIDIA-Nemotron-3-Nano-30B-A3B``): a layer is ONE part
  alone, RMSNorm and a Mamba-2 mixer, an attention layer or an expert
  layer, added to the residual; untied head, no multipliers.

The parts:

* attention: ``num_attention_heads`` query heads of ``head_dim`` over
  ``num_key_value_heads`` key/value heads (query head ``i`` reads KV head
  ``i // group``), no rotary and no position term (``nope``), scores
  scaled by ``attention_multiplier``;
* Mamba-2: ``in_proj`` gives ``z`` (the gate), ``xBC`` (through a causal
  depthwise convolution of ``mamba_d_conv`` taps and a SiLU: the heads'
  inputs ``x``, and ``B`` and ``C`` of ``mamba_d_state`` for each of
  ``mamba_n_groups`` groups: head ``h`` reads group ``h // (heads /
  groups)``) and ``dt`` a head; each head carries a ``head_dim x d_state``
  state (ops/ssm); the output is gated by ``silu(z)``, RMS-normed over
  each group's channels (one gain over all of them) and projected back;
* experts (moe/held_experts.py): a sigmoid router over
  ``n_routed_experts`` outputs with a selection bias, the top
  ``num_experts_per_tok``, the chosen scores normalised and scaled by
  ``routed_scaling_factor``; the experts ``experts_held = (first, stop)``
  of them are held here (a chip's share; the router keeps its published
  width), each a squared-ReLU MLP (``relu(h U)^2 D``) of
  ``moe_intermediate_size``, and one shared expert of
  ``moe_shared_expert_intermediate_size`` beside them. What the absent
  experts would add is left out.

This file holds the configuration and the parameter tree, nothing that
runs: the serving forward over them is serving/runner.py's (the one
``_forward`` every served model goes through), the recurrence ops/ssm's.
There is no training forward here (ROADMAP B says what stays).

Parameter tree (no bias but the convolution's; ``E`` hidden, ``I`` the MLP
width, ``H``/``K`` query/KV heads of ``D``, ``Hm`` Mamba heads of ``P``,
``G`` groups, ``W = Hm*P + 2*G*d_state`` the convolved channels, ``X``
router outputs, ``M``/``S`` the routed/shared experts' width)::

    embed [V, E]    head [V, E] (untied only; else embed's transpose)
    norm_f [E]
    h_<i>/norm_1 [E]
    h_<i>/norm_2 [E], mlp: w_in [E, 2I] (gate ‖ up)   w_out [I, E]
        (``mlp_after_mixer`` only)
    h_<i>/attn (attention layers): q [E, H*D]  k, v [E, K*D]  o [H*D, E]
    h_<i>/mamba (Mamba layers): in_proj [E, Hm*P + W + Hm] (z ‖ xBC ‖ dt)
        conv_w [d_conv, W] (tap k meets the input k - d_conv + 1 tokens
        back)   conv_b [W]   A_log, D, dt_bias [Hm]   norm [Hm*P]
        out_proj [Hm*P, E]
    h_<i>/moe (expert layers): router [E, X]   router_bias [X]
        shared: up [E, S]   down [S, E]
        experts: up [held, M, E] (as the published up_proj)
                 down [held, M, E]
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class SSMHybridConfig:
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    layer_types: Tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    mamba_n_heads: int
    mamba_d_head: int
    mamba_d_state: int
    max_position_embeddings: int
    attention_multiplier: float
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    rms_norm_eps: float = 1e-5
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    head_dim: Optional[int] = None      # None: hidden_size / query heads
    tie_word_embeddings: bool = True
    mlp_after_mixer: bool = True        # False: a layer is its one part
    # the expert layers (``moe`` in layer_types)
    n_routed_experts: int = 0           # the router's width
    experts_held: Tuple[int, int] = (0, 0)  # [first, stop) of them here
    num_experts_per_tok: int = 0
    moe_intermediate_size: int = 0
    moe_shared_expert_intermediate_size: int = 0
    routed_scaling_factor: float = 1.0

    # the router and the experts' kind (moe/held_experts.py): one routing
    # group, squared ReLU, as nemotron_h's
    n_group = topk_group = 1
    mlp_hidden_act = "relu2"

    def __post_init__(self):
        unknown = set(self.layer_types) - {"mamba", "attention", "moe"}
        if unknown:
            raise ValueError(f"layer_types names {sorted(unknown)}: a layer "
                             f"is 'mamba', 'attention' or 'moe'")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_attention_heads must divide into "
                             "num_key_value_heads")
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError("mamba_n_heads must divide into mamba_n_groups")
        if self.head_dim is None:
            object.__setattr__(self, "head_dim", self.hidden_size
                               // self.num_attention_heads)
        if self.expert_layers:
            if self.mlp_after_mixer:
                raise ValueError("an expert layer is a layer of its own: "
                                 "'moe' in layer_types needs "
                                 "mlp_after_mixer False")
            first, stop = self.experts_held
            if not 0 <= first < stop <= self.n_routed_experts:
                raise ValueError(f"experts_held {self.experts_held} is no "
                                 f"range of the {self.n_routed_experts} "
                                 f"routed experts")

    # the names the server reads of every model
    @property
    def n_layer(self) -> int:
        return len(self.layer_types)

    @property
    def n_positions(self) -> int:
        return self.max_position_embeddings

    @property
    def softmax_scale(self) -> float:
        return self.attention_multiplier

    @property
    def attention_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "attention")

    @property
    def mamba_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types)
                     if t == "mamba")

    @property
    def expert_layers(self) -> Tuple[int, ...]:
        return tuple(i for i, t in enumerate(self.layer_types) if t == "moe")

    @property
    def n_held(self) -> int:
        return self.experts_held[1] - self.experts_held[0]

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self) -> int:
        """``xBC``: the heads' inputs, then ``B`` and ``C`` of each group."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state


class SSMHybridForCausalLM:
    """The model object ``init_inference`` is handed: its configuration.
    The forward that runs is the server's (module docstring)."""

    def __init__(self, config: SSMHybridConfig):
        self.config = config

    def apply(self, *args, **kwargs):
        raise NotImplementedError(
            "SSMHybridForCausalLM has no full-sequence forward: it is served "
            "through init_serving (serving/runner.py); the training forward "
            "is ROADMAP B's")


def init_params(cfg: SSMHybridConfig, key, dtype=jnp.float32, std=0.02):
    """A seeded parameter tree in the layout of the module docstring:
    matrices N(0, ``std``), norm gains 1, the router's selection bias 0,
    and Mamba-2's published dynamics: ``A_log = log U[1, 16]``,
    ``dt_bias`` the inverse softplus of a step log-uniform on [0.001,
    0.1], ``D = 1``, the convolution U(+-1/2)."""
    E, I, D = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    H, K, Hm = cfg.num_attention_heads, cfg.num_key_value_heads, \
        cfg.mamba_n_heads
    W = cfg.conv_channels
    counter = iter(range(1 << 30))

    def draw(fn, *shape):
        return fn(jax.random.fold_in(key, next(counter)), shape)

    def w(*shape):
        return (std * draw(jax.random.normal, *shape)).astype(dtype)

    def uniform(lo, hi, *shape):
        return draw(lambda k, s: jax.random.uniform(k, s, minval=lo,
                                                    maxval=hi), *shape)


    tree = {"embed": w(cfg.vocab_size, E), "norm_f": jnp.ones((E,), dtype)}
    if not cfg.tie_word_embeddings:
        tree["head"] = w(cfg.vocab_size, E)
    for i, kind in enumerate(cfg.layer_types):
        layer = {"norm_1": jnp.ones((E,), dtype)}
        if cfg.mlp_after_mixer:
            layer["norm_2"] = jnp.ones((E,), dtype)
            layer["mlp"] = {"w_in": w(E, 2 * I), "w_out": w(I, E)}
        if kind == "attention":
            layer["attn"] = {"q": w(E, H * D), "k": w(E, K * D),
                             "v": w(E, K * D), "o": w(H * D, E)}
        elif kind == "moe":
            X, M = cfg.n_routed_experts, cfg.moe_intermediate_size
            S = cfg.moe_shared_expert_intermediate_size
            layer["moe"] = {
                "router": w(E, X), "router_bias": jnp.zeros((X,), dtype),
                "shared": {"up": w(E, S), "down": w(S, E)},
                "experts": {"up": w(cfg.n_held, M, E),
                            "down": w(cfg.n_held, M, E)}}
        else:
            step = jnp.exp(uniform(math.log(1e-3), math.log(1e-1), Hm))
            layer["mamba"] = {
                "in_proj": w(E, cfg.mamba_inner + W + Hm),
                "conv_w": uniform(-0.5, 0.5, cfg.mamba_d_conv,
                                  W).astype(dtype),
                "conv_b": uniform(-0.5, 0.5, W).astype(dtype),
                "A_log": jnp.log(uniform(1.0, 16.0, Hm)).astype(dtype),
                "D": jnp.ones((Hm,), dtype),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dtype),
                "norm": jnp.ones((cfg.mamba_inner,), dtype),
                "out_proj": w(cfg.mamba_inner, E)}
        tree[f"h_{i}"] = layer
    return tree
