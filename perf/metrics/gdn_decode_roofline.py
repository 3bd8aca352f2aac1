"""Roofline share (%) of the Gated DeltaNet decode update over the traced
interval, whatever kernel implements it (``qwen3_next_costs.UPDATE_KERNELS``:
today ``kda_decode_update*``, the Pallas kernel that reads and writes the
state plane in place, given the decay broadcast over a head's channels).
Least work per call = per layer: the RUNNING rows (``/debug/state``
samples during the capture; the padded rows of the batch are the
kernel's own waste) each move their ``[Hv, d, d]`` float32 state once in
and once out, plus the operands (``qwen3_next_costs.gdn_decode_cost``),
times the calls the trace shows. Heads and head size are the family's
``geometry``; a family without key heads apart (``Hlk``) reads nothing."""
from perf.metrics import kimi_linear_costs, qwen3_next_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    g = family_of(run.config).geometry(run.config)
    if "Hlk" not in g:
        return None
    for prefix in costs.UPDATE_KERNELS:
        share = kimi_linear_costs.decode_kernel_share(
            run, "gdn_decode_roofline", prefix,
            lambda ctx: costs.gdn_decode_cost(len(ctx), g["Hl"], g["Hlk"], g["dl"]))
        if share is not None:
            return share
    return None
