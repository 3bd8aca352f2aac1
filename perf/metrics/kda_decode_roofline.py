"""Roofline share (%) of the KDA decode update (``kda_decode_update*``,
the Pallas kernel that reads and writes the state plane in place) over
the traced interval. Least work per call = per layer: the RUNNING rows
(``/debug/state`` samples during the capture; the padded rows of the
batch are the kernel's own waste) each move their ``[H, d, d]`` float32
state once in and once out, plus q, k, v, decay, beta and the output
(``kimi_linear_costs.kda_decode_cost``), times the calls the trace
shows. Heads and head size are the family's ``geometry``."""
from perf.metrics import kimi_linear_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    g = family_of(run.config).geometry(run.config)
    if "Hl" not in g:
        return None
    return costs.decode_kernel_share(
        run, "kda_decode_roofline", "kda_decode_update",
        lambda ctx: costs.kda_decode_cost(len(ctx), g["Hl"], g["dl"]))
