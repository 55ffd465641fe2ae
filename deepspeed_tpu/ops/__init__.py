"""Public ``deepspeed_tpu.ops`` surface (reference deepspeed/ops/
__init__.py): the op family submodules plus the fused transformer layer
re-exports. Submodules load lazily — adam/lamb pull in the JIT builder
machinery, which top-level ``import deepspeed_tpu`` should not pay for."""

import importlib

_SUBMODULES = ("adam", "adagrad", "lamb", "aio", "quantizer", "ssm",
               "sparse_attention", "transformer", "op_builder")


def __getattr__(name):
    if name in _SUBMODULES:
        return importlib.import_module(f"{__name__}.{name}")
    if name in ("DeepSpeedTransformerLayer", "DeepSpeedTransformerConfig"):
        mod = importlib.import_module(f"{__name__}.transformer.transformer")
        return getattr(mod, name)
    raise AttributeError(name)
