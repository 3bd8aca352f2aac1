"""Batches of state slots for the tests of the state plane's decode
kernels (``ops/kda.py``, ``ops/conv_tail.py``): where the padded rows
stand (slot 0; they sit at position 0, so the engine flags them
``fresh`` too), and what no kernel may touch."""

import numpy as np

SLOTS = 6   # of the test planes, slot 0 included

# name -> (slots, fresh)
CASES = {
    "no_padding": ([2, 4, 1, 5, 3], [0, 1, 0, 0, 0]),
    "trailing": ([2, 4, 1, 0, 0], [0, 1, 0, 1, 1]),
    "leading": ([0, 0, 2, 4, 1], [1, 1, 0, 0, 1]),
    "interleaved": ([0, 2, 0, 0, 4, 0, 1, 0], [1, 0, 1, 1, 0, 1, 0, 1]),
    "fresh_beside_padding": ([3, 0, 5, 0], [1, 1, 1, 1]),
    "all_padded": ([0, 0, 0, 0], [1, 1, 1, 1]),
}


def case(name):
    slots, fresh = CASES[name]
    return np.asarray(slots, np.int32), np.asarray(fresh, np.int32)


def check_plane(new, plane, layer, slots, want, atol):
    """Live rows' slots of ``layer`` hold ``want``'s rows; slot 0, every
    slot no live row names and every other layer are bit-for-bit what
    they were."""
    new, live = np.asarray(new), slots != 0
    if live.any():
        np.testing.assert_allclose(
            new[layer, slots[live]], np.asarray(want)[live], atol=atol)
    untouched = np.ones(plane.shape[:2], bool)
    untouched[layer, slots[live]] = False
    assert np.array_equal(new[untouched], plane[untouched])


def check_rows(got, want, slots, atol):
    """Live rows' outputs are the oracle's, padded rows' are finite
    (zeros: they flow on through norms and projections)."""
    got, live = np.asarray(got), slots != 0
    np.testing.assert_allclose(got[live], np.asarray(want)[live], atol=atol)
    assert np.isfinite(got).all() and not got[~live].any()
