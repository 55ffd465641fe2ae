"""Binding of the ``granite_4_0_h`` configurations to the program under
test: which public objects of ``deepspeed_tpu`` run a configuration file.
Everything else the benchmark knows about the model lives in
``reference/granite_4_0_h.py``."""


def model(config: dict):
    import jax.numpy as jnp
    from deepspeed_tpu.models.ssm_hybrid import (SSMHybridConfig,
                                                 SSMHybridForCausalLM)
    from deepspeed_tpu.serving.kv_cache import PagedKVCache
    from deepspeed_tpu.serving.runner import cache_layers, cache_rows
    if config["attention_bias"] or config["mamba_proj_bias"] \
            or not config["mamba_conv_bias"]:
        raise ValueError("the program's projections have no bias and its "
                         "convolution one")
    if config["num_local_experts"]:
        raise ValueError("experts: the program's layers are dense")
    if config["position_embedding_type"] != "nope":
        raise ValueError(f"position_embedding_type "
                         f"{config['position_embedding_type']!r}: the "
                         f"program's attention layers take no positions")
    if not config["tie_word_embeddings"]:
        raise ValueError("an untied head: the program's head is the "
                         "embedding's transpose")
    cfg = SSMHybridConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        intermediate_size=config["intermediate_size"],
        layer_types=tuple(config["layer_types"]),
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        mamba_n_heads=config["mamba_n_heads"],
        mamba_d_head=config["mamba_d_head"],
        mamba_d_state=config["mamba_d_state"],
        mamba_n_groups=config["mamba_n_groups"],
        mamba_d_conv=config["mamba_d_conv"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"],
        embedding_multiplier=config["embedding_multiplier"],
        attention_multiplier=config["attention_multiplier"],
        residual_multiplier=config["residual_multiplier"],
        logits_scaling=config["logits_scaling"])
    if cfg.n_layer != config["num_hidden_layers"]:
        raise ValueError(f"layer_types names {cfg.n_layer} layers, "
                         f"num_hidden_layers {config['num_hidden_layers']}")
    if cfg.n_positions != config["n_positions"]:
        raise ValueError("n_positions is not max_position_embeddings")
    if cfg.mamba_inner != config["mamba_expand"] * config["hidden_size"]:
        raise ValueError(f"{cfg.mamba_n_heads} Mamba heads of "
                         f"{cfg.mamba_d_head} are not mamba_expand x "
                         f"hidden_size")
    kinds = config["layer_types"]
    if cache_layers(cfg) != {"paged": kinds.count("attention"),
                             "per_slot": kinds.count("mamba")}:
        raise ValueError(f"the program caches {cache_layers(cfg)} layers, "
                         f"the file's layer_types differ")
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
    if cfg.softmax_scale != config["attention_multiplier"]:
        raise ValueError(f"the program scales scores by {cfg.softmax_scale}, "
                         f"the file by {config['attention_multiplier']}")
    return SSMHybridForCausalLM(cfg)
