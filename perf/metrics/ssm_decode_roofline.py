"""Roofline share (%) of the Mamba-2 decode update (``ssm_decode_update*``,
the Pallas kernel that reads and writes the state plane in place) over
the traced interval. Least work per call = per layer: the RUNNING rows
(``/debug/state`` samples during the capture; the padded rows of the
batch are the kernel's own waste) each move their ``[Hm, P, N]`` float32
state once in and once out, plus the operands
(``nemotron_h_costs.ssm_decode_cost``), times the calls the trace shows.
The sizes are the family's ``geometry``; a family without a state size
apart from its head size (``N``) reads nothing, and so does a program
that runs no such kernel."""
from perf.metrics import kimi_linear_costs, nemotron_h_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    g = family_of(run.config).geometry(run.config)
    if "Hm" not in g or "N" not in g:
        return None
    return kimi_linear_costs.decode_kernel_share(
        run, "ssm_decode_roofline", "ssm_decode_update",
        lambda ctx: costs.ssm_decode_cost(
            len(ctx), g["Hm"], g["P"], g["N"], g["G"]))
