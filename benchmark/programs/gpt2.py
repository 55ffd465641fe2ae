"""Binding of the ``gpt2`` configurations to the program under test: which
public objects of ``deepspeed_tpu`` run a configuration file. Everything
else the benchmark knows about the model lives in ``reference/gpt2.py``."""


def model(config: dict):
    from deepspeed_tpu.models.gpt2 import PRESETS, GPT2Config, GPT2LMHeadModel
    cfg = GPT2Config(vocab_size=config["vocab_size"],
                     n_positions=config["n_positions"],
                     n_embd=config["n_embd"], n_layer=config["n_layer"],
                     n_head=config["n_head"],
                     dropout=config["resid_pdrop"],
                     use_flash=config.get("use_flash"))
    preset = config.get("preset")
    if preset is not None and PRESETS[preset] != cfg:
        raise ValueError(f"configuration file and the program's preset "
                         f"{preset!r} disagree: {cfg} vs {PRESETS[preset]}")
    if cfg.padded_vocab != config["assumed"]["padded_vocab_size"]:
        raise ValueError(f"the program pads the vocabulary to "
                         f"{cfg.padded_vocab}, the file assumes "
                         f"{config['assumed']['padded_vocab_size']}")
    return GPT2LMHeadModel(cfg)


def tensor_parallel_rules():
    from deepspeed_tpu.models.gpt2 import gpt2_tp_rules
    from deepspeed_tpu.runtime.zero.partition import ModelParallelRules
    return ModelParallelRules(gpt2_tp_rules())
