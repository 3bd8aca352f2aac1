"""``ops/conv_tail.py`` ``conv_tail_update`` (interpreted) against the
XLA lines of ``models/hybrid.py`` ``conv_step``, which the prefill path
and a host without the kernels keep: the three families' channel counts
(and one that is no multiple of 128, as the tiny test models have), with
and without a bias, padded rows anywhere in the batch."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models import hybrid
from tests import state_plane_cases as spc

K = 4
CHANNELS = {"kimi": 12288, "qwen3_next": 8192, "nemotron": 6144, "tiny": 96}


@pytest.mark.parametrize("bias", [False, True], ids=["no_bias", "bias"])
@pytest.mark.parametrize("family", sorted(CHANNELS))
@pytest.mark.parametrize("name", sorted(spc.CASES))
def test_the_tail_kernel_is_the_inline_convolution_step(name, family, bias):
    C = CHANNELS[family]
    rng = np.random.default_rng(4)
    slots, fresh = spc.case(name)
    B = len(slots)
    plane = rng.normal(size=(2, spc.SLOTS, *hybrid.conv_tail_shape(K, C))
                       ).astype(np.float32)
    x = jnp.asarray(rng.normal(size=(B, 1, C)).astype(np.float32))
    cw = jnp.asarray(rng.normal(size=(K, C)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(C,)).astype(np.float32)) if bias else None
    n_valid = jnp.asarray((slots != 0).astype(np.int32))

    def step(kernels):
        return hybrid.conv_step(jnp.asarray(plane), 1, jnp.asarray(slots),
                                jnp.asarray(fresh) != 0, n_valid, x, cw, b,
                                kernels=kernels)

    y_want, plane_want = step(False)
    y, new = step(True)
    assert y.shape == y_want.shape == (B, 1, C) and y.dtype == jnp.float32
    spc.check_rows(y, y_want, slots, atol=1e-5)
    spc.check_plane(new, plane, 1, slots, np.asarray(plane_want)[1, slots], atol=0)


def test_a_slot_is_stored_as_whole_lane_tiles():
    for C in (12288, 8192, 6144):
        rows, lane = hybrid.conv_tail_shape(K, C)
        assert lane == 128 and rows * lane == (K - 1) * C and (rows // (K - 1)) % 8 == 0
    assert hybrid.conv_tail_shape(K, 96) == (3, 96)
