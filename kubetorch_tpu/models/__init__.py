"""Model zoo: TPU-first implementations used by examples, benches, and tests.

Functional style (pure init/apply over pytrees) rather than a module
framework: params are plain nested dicts whose leaves carry *logical axis*
metadata via :func:`kubetorch_tpu.models.llama.param_logical_axes`, so any
parallel layout in :mod:`kubetorch_tpu.parallel` applies without touching
model code. Layers are stacked and scanned (``lax.scan``) so compile time is
O(1) in depth.
"""

from kubetorch_tpu.models.configs import (HybridLatentMoEConfig,
                                          HybridLinearConfig,
                                          IndexedMoEConfig, LatentMoEConfig,
                                          LlamaConfig, MoEConfig, ViTConfig,
                                          WindowMoEConfig)
from kubetorch_tpu.models import llama


def __getattr__(name):
    # generate pulls in the sampling stack; keep the train-only import
    # light. importlib, not `from … import`: the latter consults this very
    # __getattr__ before importing, recursing forever on module names.
    import importlib

    if name in ("generate", "quant", "rolling", "speculative", "lora",
                "embed", "decoder", "experts", "latent_moe",
                "hybrid_linear", "window_moe", "indexed_moe",
                "hybrid_latent_moe"):
        return importlib.import_module(f"kubetorch_tpu.models.{name}")
    if name == "LoraConfig":
        return importlib.import_module(
            "kubetorch_tpu.models.lora").LoraConfig
    if name == "Generator":
        return importlib.import_module(
            "kubetorch_tpu.models.generate").Generator
    if name == "SpeculativeGenerator":
        return importlib.import_module(
            "kubetorch_tpu.models.speculative").SpeculativeGenerator
    if name == "quantize_params":
        return importlib.import_module(
            "kubetorch_tpu.models.quant").quantize_params
    if name == "RollingGenerator":
        return importlib.import_module(
            "kubetorch_tpu.models.rolling").RollingGenerator
    if name == "Embedder":
        return importlib.import_module(
            "kubetorch_tpu.models.embed").Embedder
    raise AttributeError(name)


__all__ = ["LlamaConfig", "MoEConfig", "LatentMoEConfig",
           "HybridLinearConfig", "WindowMoEConfig", "IndexedMoEConfig",
           "HybridLatentMoEConfig",
           "ViTConfig", "decoder", "experts", "latent_moe", "hybrid_linear",
           "window_moe", "indexed_moe", "hybrid_latent_moe", "llama",
           "Generator",
           "generate", "quant", "quantize_params", "RollingGenerator",
           "SpeculativeGenerator", "speculative", "lora", "LoraConfig",
           "embed", "Embedder"]
