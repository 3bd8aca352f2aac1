"""Model families: pure-JAX decoder implementations with mesh shardings.

The reference delegates model execution to external engines (vLLM/SGLang/
TRT-LLM, reference: SURVEY.md §1 L3); dynamo-tpu's flagship engine is
native: functional JAX models (params as pytrees), lax.scan over layers for
fast compiles, paged KV cache, and named-axis shardings so pjit/XLA place
the collectives.
"""

from dynamo_tpu.models.config import ModelConfig


def family(cfg: ModelConfig):
    """The module that holds a configuration's parameters, cache and
    step: ``models/kimi_linear.py`` for ``model_type`` ``kimi_linear``,
    ``models/llama.py`` for every other. Both give ``param_shapes``,
    ``param_specs``, ``init_params``, ``init_cache`` and ``forward``."""
    if cfg.model_type == "kimi_linear":
        from dynamo_tpu.models import kimi_linear

        return kimi_linear
    from dynamo_tpu.models import llama

    return llama


__all__ = ["ModelConfig", "family"]
