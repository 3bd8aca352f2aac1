"""The decode roofline byte-budget model behind the serving process's
own gauges.

The attribution ledger (telemetry/attribution.py) publishes *achieved
decode tok/s over the HBM byte-bound roofline* live as
``dynamo_roofline_frac`` and splits measured device time by this
module's per-phase byte prior. The benchmark does not read it:
``perf/roofline.py`` counts a kernel's operations and bytes against a
device trace (PERF.md section 3).

The model (kv_dtype- and quant-aware):

- ``param_bytes``: every decode step reads all weights once — layer
  matmuls + embedding + LM head, at 1 B/elem for int8 weight-only
  quant, 2 B/elem for bf16.
- ``kv_bytes_per_token``: each sequence's KV window is read per step —
  ``2·L·Hk·Dh`` elements/token at the cache dtype (int8 pays the
  per-(slot, head) f32 scale: ``+4/Dh`` per element; fp8 is scale-free).
- ``step_bytes`` = weights + batch·ctx·kv_bytes_per_token; roofline
  tok/s = ``batch / (step_bytes / HBM_BW_BYTES)``.
- ``phase_ideal_bytes`` splits the same budget into the four decode
  phases (attention / MLP+projections / LM head / sampling) — the cost
  prior the attribution ledger uses to split measured device time.
"""

from __future__ import annotations

from dataclasses import dataclass

@dataclass(frozen=True)
class DevicePeaks:
    hbm_bytes_per_s: float
    bf16_flops_per_s: float
    hbm_bytes: float


# Published per-chip peaks, keyed by ``jax.Device.device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (system architecture):
# 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s per chip. JAX reports a v5e
# chip as "TPU v5 lite". An accelerator whose kind is not here is an
# error (device_peaks), never a silent v5e.
_V5E = DevicePeaks(hbm_bytes_per_s=819e9, bf16_flops_per_s=197e12,
                   hbm_bytes=16e9)
DEVICE_PEAKS: dict[str, DevicePeaks] = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}

# CPU backends (tests, dev runs) have no roofline of their own: they
# keep the v5e row as a NAMED default so CPU-side ledger arithmetic
# stays comparable; nothing computed from it there is a device metric.
CPU_DEFAULT_KIND = "TPU v5 lite"
HBM_BW_BYTES = DEVICE_PEAKS[CPU_DEFAULT_KIND].hbm_bytes_per_s


def device_peaks(device) -> DevicePeaks:
    """Peaks of ``device`` (a ``jax.Device``); raises for an
    accelerator the table does not know."""
    if device.platform == "cpu":
        return DEVICE_PEAKS[CPU_DEFAULT_KIND]
    try:
        return DEVICE_PEAKS[device.device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device.device_kind!r} "
            f"(platform {device.platform!r}); add its datasheet row to "
            f"telemetry.roofline.DEVICE_PEAKS (known: "
            f"{sorted(DEVICE_PEAKS)})"
        ) from None


# decode phases, in step order (docs/performance.md byte table)
PHASES = ("attention", "mlp", "lm_head", "sampling")

_FP8_DTYPES = ("fp8", "float8", "float8_e4m3fn", "float8_e5m2")


def weight_bytes_per_elem(quant: str | None) -> int:
    return 1 if quant == "int8" else 2


def param_bytes(mc, quant: str | None) -> int:
    """Total weight bytes one decode step must stream: all layer matmul
    weights plus the embedding and LM head (``2·V·D``)."""
    D, F, V, L = (
        mc.hidden_size, mc.intermediate_size, mc.vocab_size,
        mc.num_hidden_layers,
    )
    H, Hk, Dh = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
    per_layer = D * H * Dh + 2 * D * Hk * Dh + H * Dh * D + 3 * D * F
    return weight_bytes_per_elem(quant) * (per_layer * L + 2 * V * D)


def kv_bytes_per_token(mc, kv_dtype: str) -> float:
    """HBM bytes per cached token position (both K and V, all layers).
    int8 carries the per-(slot, head) f32 scale the Pallas decode kernel
    reads alongside the page (ops/kv_quant.py layout)."""
    if kv_dtype in _FP8_DTYPES:
        per_elem = 1.0
    elif kv_dtype == "int8":
        per_elem = 1.0 + 4.0 / mc.head_dim
    else:
        per_elem = 2.0
    return (
        2 * mc.num_hidden_layers * mc.num_key_value_heads * mc.head_dim
        * per_elem
    )


def step_bytes(
    mc, batch: int, avg_ctx: float, quant: str | None, kv_dtype: str,
) -> float:
    """Ideal HBM traffic of one decode step: weights once + each
    sequence's KV window at the average context length."""
    return param_bytes(mc, quant) + batch * avg_ctx * kv_bytes_per_token(
        mc, kv_dtype
    )


def roofline_tok_s(
    mc, batch: int, avg_ctx: float, quant: str | None, kv_dtype: str,
    hbm_bw: float = HBM_BW_BYTES,
) -> float:
    """Byte-bound decode throughput ceiling: ``batch`` tokens per
    ``step_bytes / hbm_bw`` seconds."""
    return batch / (step_bytes(mc, batch, avg_ctx, quant, kv_dtype) / hbm_bw)


def phase_ideal_bytes(
    mc, batch: int, avg_ctx: float, quant: str | None, kv_dtype: str,
) -> dict[str, int]:
    """The step byte budget split by decode phase — the table in
    docs/performance.md, and the device-time cost prior the attribution
    ledger splits measured compute with. ``mlp`` covers ALL layer
    matmul weights (attention projections included: they stream with
    the MLP weights, distinct from the KV *cache* reads billed to
    ``attention``); ``lm_head`` is the single ``D·V`` read plus the
    per-channel scales under int8; ``sampling`` is the ``[B, V]`` f32
    logits."""
    D, F, V, L = (
        mc.hidden_size, mc.intermediate_size, mc.vocab_size,
        mc.num_hidden_layers,
    )
    H, Hk, Dh = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
    wb = weight_bytes_per_elem(quant)
    layer_weights = (D * H * Dh + 2 * D * Hk * Dh + H * Dh * D + 3 * D * F) * wb
    return {
        "attention": int(batch * avg_ctx * kv_bytes_per_token(mc, kv_dtype)),
        "mlp": int(layer_weights * L),
        "lm_head": int(D * V * wb + (V * 4 if quant == "int8" else 0)),
        "sampling": int(batch * V * 4),
    }


@dataclass(frozen=True)
class RooflineModel:
    """The scalars the attribution ledger needs per step, derived once
    at engine init so the hot path never touches the model config:
    ``ideal_step_s(batch, context_tokens)`` (the roofline denominator —
    ``param_bytes``, embedding included) and the device-phase split
    prior. ``mlp_bytes`` is the LAYER matmul weights only — the same
    set ``phase_ideal_bytes`` bills to ``mlp`` (the embedding gather
    reads B rows, not the table, so it belongs in neither phase)."""

    param_bytes: float
    kv_bytes_per_token: float
    mlp_bytes: float
    lm_head_bytes: float
    sampling_bytes_per_row: float
    hbm_bw: float = HBM_BW_BYTES

    def ideal_step_s(self, batch: int, context_tokens: float) -> float:
        """Byte-bound time of one decode step over ``batch`` rows whose
        context lengths sum to ``context_tokens``."""
        total = (
            self.param_bytes
            + context_tokens * self.kv_bytes_per_token
            + batch * self.sampling_bytes_per_row
        )
        return total / self.hbm_bw

    def phase_fractions(
        self, batch: int, context_tokens: float
    ) -> dict[str, float]:
        """Per-phase byte shares of one step at the live geometry — the
        prior used to split measured device time."""
        b = {
            "attention": context_tokens * self.kv_bytes_per_token,
            "mlp": self.mlp_bytes,
            "lm_head": self.lm_head_bytes,
            "sampling": batch * self.sampling_bytes_per_row,
        }
        total = sum(b.values()) or 1.0
        return {k: v / total for k, v in b.items()}


def build_roofline(
    mc, quant: str | None, kv_dtype: str, hbm_bw: float = HBM_BW_BYTES,
) -> RooflineModel:
    wb = weight_bytes_per_elem(quant)
    ph = phase_ideal_bytes(mc, 1, 0, quant, kv_dtype)
    return RooflineModel(
        param_bytes=float(param_bytes(mc, quant)),
        kv_bytes_per_token=kv_bytes_per_token(mc, kv_dtype),
        mlp_bytes=float(ph["mlp"]),
        lm_head_bytes=float(ph["lm_head"]),
        sampling_bytes_per_row=float(mc.vocab_size * 4),
        hbm_bw=hbm_bw,
    )
