"""Roofline share (%) of the latent-attention decode kernel
(``mla_decode_attention*``) over the traced interval. Least work per call
= per layer: every cached row of the running rows' contexts
(``/debug/state`` samples during the capture) is read once — ``rank +
rope`` values of 2 bytes, key and value at once — plus the queries in and
the latent-space output out (``kimi_linear_costs.mla_decode_cost``),
times the calls the trace shows. The kernel reads whole 128-token pages;
the unused tail of a row's last page is its own waste, not least work."""
from perf.metrics import kimi_linear_costs as costs
from perf.reference.family import family_of


def read(run, variant=""):
    g = family_of(run.config).geometry(run.config)
    if "rank" not in g:
        return None
    return costs.decode_kernel_share(
        run, "mla_decode_roofline", "mla_decode_attention",
        lambda ctx: costs.mla_decode_cost(ctx, g["H"], g["rank"], g["rope"]))
