"""Binding of the ``dots_vlm1`` configurations to the program under test:
which public objects of ``deepspeed_tpu`` run a configuration file.
Everything else the benchmark knows about the model lives in
``reference/dots_vlm1.py``."""


def model(config: dict):
    from deepspeed_tpu.models.mla_moe import MLAMoEConfig, MLAMoEForCausalLM
    from deepspeed_tpu.serving.kv_cache import PagedKVCache
    from deepspeed_tpu.serving.runner import cache_rows
    rs = config["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"rope_scaling type {rs['type']!r}: the program "
                         f"computes YaRN frequencies")
    cfg = MLAMoEConfig(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["deployment"]["experts_held"]),
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        n_group=config["n_group"], topk_group=config["topk_group"],
        routed_scaling_factor=config["routed_scaling_factor"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"],
        max_position_embeddings=config["max_position_embeddings"],
        rms_norm_eps=config["rms_norm_eps"], rope_theta=config["rope_theta"],
        rope_factor=rs["factor"],
        rope_original_max_position=rs["original_max_position_embeddings"],
        rope_beta_fast=rs["beta_fast"], rope_beta_slow=rs["beta_slow"],
        rope_mscale=rs["mscale"], rope_mscale_all_dim=rs["mscale_all_dim"])
    if cfg.n_held != config["n_routed_experts"]:
        raise ValueError(
            f"the file holds {config['n_routed_experts']} routed experts, "
            f"its deployment {cfg.n_held} ({cfg.experts_held})")
    if cfg.n_positions != config["n_positions"]:
        raise ValueError("n_positions is not max_position_embeddings")
    assumed = config["assumed"]
    lanes = PagedKVCache(n_layer=1, block_size=16, num_blocks=2,
                         **cache_rows(cfg)).row_width
    if lanes != assumed["latent_row_lanes"]:
        raise ValueError(f"the program caches rows of {lanes} lanes, the "
                         f"file assumes {assumed['latent_row_lanes']}")
    if abs(cfg.softmax_scale - assumed["softmax_scale"]) > 1e-6:
        raise ValueError(f"the program scales scores by {cfg.softmax_scale}, "
                         f"the file assumes {assumed['softmax_scale']}")
    return MLAMoEForCausalLM(cfg)
