"""Share (%) of the decode buckets' rows that were padding, over the decode
dispatches of the UNTRACED window: the growth of ``decode_rows_padded``
over that of ``decode_rows_dispatched`` (``program_spans.json``
``history``; perf/trace/count_history.py). The engine pads a decode batch
to one of a few row buckets; the state plane's kernels (``ops/kda.py``,
``ops/conv_tail.py``) move nothing for a padded row, so this is how much
of a bucket's recurrent-state traffic they are spared — and a bucket
that fits the load better would read lower. A program that keeps no
such counts (an older commit) gives nothing to read, and neither does a
window without a decode dispatch."""
from perf.trace import count_history as ch

ROWS, PADDED = "decode_rows_dispatched", "decode_rows_padded"


def read(run, variant=""):
    g = ch.growth(run)
    if g is None or ROWS not in g or PADDED not in g:
        return None
    rows = g[ROWS]
    if not rows:
        return None
    n = g.get("dispatches.decode", 0)
    run.notes.append({"decode_pad_rows_share": {
        ROWS: rows, PADDED: g[PADDED], "decode_dispatches": n,
        "rows_a_dispatch": round(rows / n, 3) if n else None,
        "live_rows_a_dispatch": round((rows - g[PADDED]) / n, 3) if n else None}})
    return 100.0 * g[PADDED] / rows
