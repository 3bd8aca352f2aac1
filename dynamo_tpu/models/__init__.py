"""Model families: pure-JAX decoder implementations with mesh shardings.

The reference delegates model execution to external engines (vLLM/SGLang/
TRT-LLM, reference: SURVEY.md §1 L3); dynamo-tpu's flagship engine is
native: functional JAX models (params as pytrees), lax.scan over layers for
fast compiles, paged KV cache, and named-axis shardings so pjit/XLA place
the collectives.

THE CONTRACT OF A FAMILY MODULE (what ``family`` returns; the engine and
the loader reach a model through nothing else):

- ``param_shapes(cfg)``, ``param_specs(cfg)``, ``init_params(cfg, seed,
  mesh, specs)``: the parameter pytree and its shardings;
- ``init_cache(cfg, num_blocks, block_size, mesh, dtype, spec[,
  state_slots])``: the two cache pytrees the step threads through and
  donates (K and V pages; or a family's pages and its state plane);
- ``forward(cfg, params, cache_a, cache_b, tokens, positions,
  slot_mapping, block_tables, context_lens, last_token_idx, block_size,
  ...) -> (logits, cache_a, cache_b)``: one model step;
- optionally ``FAMILIES``: the ``model_type`` values it serves beside its
  own file name.

A family that draws its own parameters says so, and gives the engine
what it sizes and checks with. THREE further questions are asked of it,
each on its own (``ModelConfig.owns_pages``, ``.has_recurrent_state``,
``.released_window``):

- ``init_params_quantized(cfg, seed, mesh, specs)`` (the loader then
  takes random weights only);
- DOES IT LAY ITS PAGES OUT ITSELF? ``page_bytes_per_block(cfg,
  block_size, itemsize)`` and ``STEP_TRANSIENT_BYTES`` say so: what a
  block of its pages (latent rows, or the K and V of its attention layers
  alone) and a step's temporaries take. The engine sizes the pool with
  them, and refuses what moves K/V pages by their llama layout: block
  export / import (``engine.refuse_kv_transfer``), KVBM offload and int8
  pages (``check_engine``). The allocator, the scheduler, chunked prefill
  and the PREFIX CACHE treat such pages like any others: a hit's pages
  are read by the prefill of the row's remaining tokens (a start position
  > 0 over cached pages is what a second prefill chunk already is), KV
  events are published, a preempted row resumes from its cached pages
  (``models/deepseek_v3.py``: latent pages and nothing else);
- DOES IT KEEP RECURRENT STATE? ``RECURRENT_STATE = True`` and
  ``state_bytes(cfg, state_slots, itemsize)``: every admitted sequence
  gets a slot of the state plane (``init_cache(..., state_slots)``), the
  table's last column; prefix reuse is off, because a cached page prefix
  is worth nothing without the state at its end
  (``models/kimi_linear.py``, ``models/qwen3_next.py``,
  ``models/nemotron_h.py``: all three also own their pages);
- DOES A PLANE OF ITS PAGES RELEASE BEHIND A WINDOW? ``released_window(
  cfg)`` gives the window (0: none does), ``page_bytes_per_block(...,
  plane="window")`` that plane's bytes and ``init_cache(...,
  window_blocks)`` its pages: layers that attend only the last
  ``window`` keys keep their K and V in a SECOND plane with its own
  block ids (``engine/allocator.py`` ``WindowPlane``), whose table rides
  as the second half of ``block_tables`` by the same absolute column and
  whose pages go back as they fall behind ``p - (window - 1)`` for the
  row's next query ``p`` — after each prefill chunk and as decode
  advances — so a row holds the window's pages and a dispatch's
  look-ahead of that plane, never its length. Admission reserves every
  admitted row's bound of it beside the full plane's timeline,
  preemption, cancellation and finish free both. Prefix reuse is off,
  because a cached full-plane prefix is worth nothing without the
  window plane's last pages (every admission a counted miss, as the
  state families), and what moves pages by their llama layout stays
  refused (``models/mimo_v2_flash.py``: it owns its pages and keeps no
  recurrent state);
- ``check_engine(engine_config)``: raises for what it does not build;
- ``COUNT_NAMES``: the cumulative int32 counts it keeps on the device in
  ``cache_b["counts"]`` (``engine.program_counts``; the once-a-second
  count history repeats them as last read).
"""

import functools
import importlib
import importlib.util
import pkgutil

from dynamo_tpu.models.config import ModelConfig

REQUIRED = ("param_shapes", "param_specs", "init_params", "init_cache", "forward")


def _is_family(module) -> bool:
    return all(hasattr(module, name) for name in REQUIRED)


def family(cfg: ModelConfig):
    """The module that holds a configuration's parameters, cache and
    step: ``models/<model_type>.py`` (``-`` read as ``_``) where that
    file is a family module, else the one whose ``FAMILIES`` lists the
    ``model_type`` (``models/llama.py``: the dense decoders), else an
    error that names what exists. The contract is this module's
    docstring."""
    return _family_of(str(cfg.model_type))


@functools.lru_cache(maxsize=None)
def _family_of(model_type: str):
    stem = model_type.replace("-", "_")
    if stem.isidentifier() and importlib.util.find_spec(f"{__name__}.{stem}"):
        module = importlib.import_module(f"{__name__}.{stem}")
        if _is_family(module):
            return module
    from dynamo_tpu.models import llama

    if model_type in llama.FAMILIES:
        return llama
    served = {}
    for info in pkgutil.iter_modules(__path__):
        module = importlib.import_module(f"{__name__}.{info.name}")
        if _is_family(module):
            served[info.name] = sorted(getattr(module, "FAMILIES", ()))
    raise LookupError(
        f"no model family for model_type {model_type!r}: no family module "
        f"dynamo_tpu/models/{stem}.py, and no FAMILIES lists it "
        f"(family modules and what they list: {served})"
    )


__all__ = ["ModelConfig", "family"]
