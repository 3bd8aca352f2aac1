"""Share (%) of the tokens that the dispatched prefill rectangles padded
to which were real prompt tokens: ``prefill_tokens_real`` over
``prefill_tokens_padded`` (``program_spans.json`` counts; the engine adds
a rectangle's chunks' tokens to the first and its rows x tokens to the
second as it dispatches it; start-up's warming dispatches are not
counted). Near 100 a prefill step does only its own work; a low share is
device time spent on padding, which every decode row and every arrival
behind the step waits out.

ONE interval in every cell: everything the server dispatched up to the
capture's end, ramp and window alike — the counts as they stand there,
not their growth over the capture, because a capture of 2 s holds a dozen
prefill steps in a closed loop and none at all in the chat schedule. A
program that keeps no such counts (an older commit) gives nothing to
read."""
from perf.trace import program_spans


def read(run, variant=""):
    doc = program_spans.spans_doc(run)
    at_end = ((doc or {}).get("stop") or {}).get("counts") or {}
    keepers = [c for c in at_end.values() if isinstance(c, dict)]
    real = sum(c.get("prefill_tokens_real", 0) for c in keepers)
    padded = sum(c.get("prefill_tokens_padded", 0) for c in keepers)
    if not padded:
        return None
    run.notes.append({"prefill_fill_share": {
        "tokens_real": real, "tokens_padded": padded}})
    return 100.0 * real / padded
