"""Roofline share (%) of the window-attention layers' DECODE calls over
the traced interval (``mimo_v2_flash``: ``paged_attention_decode_stacked_
window*``). Least bytes: the keys those calls read, from the program's own
count ``attn_window_decode_keys`` at the capture's edges, x that kind's
KV heads x the PUBLISHED 192 + 128 values x 2 B. What is compared, when
the reader says nothing and why it can err low only:
``mimo_v2_flash_costs.py``. A program without these counts or this
kernel's name (another family, an older commit) reads nothing."""
from perf.metrics import mimo_v2_flash_costs as costs


def read(run, variant=""):
    return costs.decode_share(run, "window")
