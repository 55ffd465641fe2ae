"""Binding of the ``nemotron_h`` configurations to the program under test:
which public objects of ``deepspeed_tpu`` run a configuration file.
Everything else the benchmark knows about the model lives in
``reference/nemotron_h.py``."""

KINDS = {"M": "mamba", "*": "attention", "E": "moe"}


def model(config: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                                 SSMHybridForCausalLM)
    from deepspeed_tpu.serving.kv_cache import PagedKVCache
    from deepspeed_tpu.serving.runner import cache_layers, cache_rows
    if config["attention_bias"] or config["mamba_proj_bias"] \
            or config["mlp_bias"] or not config["use_conv_bias"]:
        raise ValueError("the program's projections have no bias and its "
                         "convolution one")
    if config["tie_word_embeddings"]:
        raise ValueError("a tied head: the program's nemotron_h head is "
                         "its own matrix")
    if config["mamba_hidden_act"] != "silu":
        raise ValueError(f"mamba_hidden_act {config['mamba_hidden_act']!r}:"
                         f" the program's convolution and gate are SiLU")
    pattern = config["hybrid_override_pattern"]
    if set(pattern) - set(KINDS):
        raise ValueError(f"hybrid_override_pattern {pattern!r}: the program "
                         f"serves layers of {sorted(KINDS)}")
    if config["n_shared_experts"] != 1:
        raise ValueError("the program's expert layer has one shared expert")
    if config["n_group"] != 1 or config["topk_group"] != 1:
        raise ValueError("group-limited routing: the program's nemotron_h "
                         "router chooses among all its experts")
    if config["mlp_hidden_act"] != "relu2":
        raise ValueError(f"mlp_hidden_act {config['mlp_hidden_act']!r}: the "
                         f"program's nemotron_h experts are squared ReLU")
    cfg = SSMHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        layer_types=tuple(KINDS[p] for p in pattern),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        head_dim=config["head_dim"],
        mamba_n_heads=config["mamba_num_heads"],
        mamba_d_head=config["mamba_head_dim"],
        mamba_d_state=config["ssm_state_size"],
        mamba_n_groups=config["n_groups"],
        mamba_d_conv=config["conv_kernel"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["layer_norm_epsilon"],
        attention_multiplier=config["head_dim"] ** -0.5,
        tie_word_embeddings=False, mlp_after_mixer=False,
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["deployment"]["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        moe_intermediate_size=config["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=config[
            "moe_shared_expert_intermediate_size"],
        routed_scaling_factor=config["routed_scaling_factor"])
    if cfg.n_layer != config["num_hidden_layers"]:
        raise ValueError(f"hybrid_override_pattern names {cfg.n_layer} "
                         f"layers, num_hidden_layers "
                         f"{config['num_hidden_layers']}")
    if cfg.n_held != config["n_routed_experts"]:
        raise ValueError(
            f"the file holds {config['n_routed_experts']} routed experts, "
            f"its deployment {cfg.n_held} ({cfg.experts_held})")
    if cfg.n_positions != config["n_positions"]:
        raise ValueError("n_positions is not max_position_embeddings")
    if cache_layers(cfg) != {"paged": pattern.count("*"),
                             "per_slot": pattern.count("M")}:
        raise ValueError(f"the program caches {cache_layers(cfg)} layers, "
                         f"the file's pattern differs")
    assumed = config["assumed"]
    state = jnp.dtype(cache_rows(cfg)["slot_state"]["ssm"][1]).name
    if state != assumed["state_dtype"]:
        raise ValueError(f"the program keeps the state in {state}, the "
                         f"file assumes {assumed['state_dtype']}")
    lanes = PagedKVCache(n_layer=1, block_size=16, num_blocks=2,
                         **cache_rows(cfg)).row_width
    if lanes != assumed["kv_row_lanes"]:
        raise ValueError(f"the program caches rows of {lanes} lanes, the "
                         f"file assumes {assumed['kv_row_lanes']}")
    return SSMHybridForCausalLM(cfg)
