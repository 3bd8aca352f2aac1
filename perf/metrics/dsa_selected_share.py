"""Share (%) of the keys the indexer scored that were then attended,
prefill and decode together, over the programs of the capture: the growth
of ``dsa_prefill_selected`` x 1 024 + ``dsa_decode_selected`` over that of
``dsa_index_pairs`` x 1 024 + ``dsa_decode_scored`` (the program's counts
at the capture's edges). It says how sparse the traffic made the layer:
a 12k-token document's cold prefill attends about 31% of the pairs it
scores, a decode step at 8k-20k 10-25%, and 100 would mean that no query
of the capture had more than 2 048 keys behind it — the cell never left
the dense regime. Read from the capture's edges and not from the
once-a-second count history: the history repeats a family's DEVICE counts
as last read (the tick reads no device array), so they grow only across
a capture. A program without these counts reads nothing."""
from perf.metrics import glm_moe_dsa_costs as costs


def read(run, variant=""):
    got = costs.counted(run, against_trace=False)
    if not got:
        return None
    scored = got["dsa_index_pairs"] * costs.PAIR_UNIT + got["dsa_decode_scored"]
    picked = got["dsa_prefill_selected"] * costs.PAIR_UNIT + got["dsa_decode_selected"]
    return 100.0 * picked / scored if scored else None
