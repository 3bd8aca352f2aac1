"""Window-plane pages in use a row, over the programs dispatched in the
UNTRACED window: the growth of ``window_page_steps`` (the plane's pages in
use, added at every dispatch) over that of ``window_row_steps`` (the rows
admitted: running and prefilling) in the count history
(``program_spans.json`` ``history``; perf/trace/count_history.py). A
handful — the window's pages, a decode look-ahead, a chunk's span while a
row prefills — whatever the rows' contexts: it is what releasing behind
the window buys, and it would read the contexts' pages if nothing were
released. A program without that plane keeps no such counts: nothing is
read."""
from perf.trace import count_history as ch


def read(run, variant=""):
    g = ch.growth(run)
    if g is None or "window_page_steps" not in g:
        return None
    rows = g.get("window_row_steps", 0)
    if not rows:
        return None
    run.notes.append({"window_pages_per_row": {
        "page_steps": g["window_page_steps"], "row_steps": rows,
        "released": g.get("window_pages_released_total"),
        "admit_blocked_window": g.get("admit_blocked_window")}})
    return g["window_page_steps"] / rows
