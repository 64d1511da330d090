"""``ops/fdiv``: a quotient a few ulps off, as the TPU's f32 divide gives
it, is moved to the IEEE quotient, so that shares IEEE makes equal tie in
the kernels' job pick on the chip as in the numpy reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from volcano_tpu.ops.allocate import namespace_share, queue_share
from volcano_tpu.ops.fdiv import correct_quotient, div_rn

F = np.float32


def _pairs(n=200_000, seed=0):
    """Operands over the magnitudes the shares divide (milli-units up to
    ~1e17), a quarter of them a / a and a quarter whole multiples, plus
    pairs whose quotient lies next to the midpoint of two floats."""
    rng = np.random.default_rng(seed)
    a = (10 ** rng.uniform(-3, 17, n)).astype(F)
    d = (10 ** rng.uniform(-3, 17, n)).astype(F)
    m = n // 4
    d[:m] = a[:m]
    a[m:2 * m] = d[m:2 * m] * rng.integers(1, 1000, m).astype(F)
    q = rng.uniform(1, 2, m).astype(F)
    mid = d[-m:].astype(np.float64) * (q + np.spacing(q) / 2.0)
    a[-m:] = mid.astype(F)
    return a, d


@pytest.mark.parametrize("ulps", [-3, -2, -1, 0, 1, 2, 3])
def test_correct_quotient_lands_on_the_ieee_quotient(ulps):
    a, d = _pairs()
    want = a / d
    q = (want.view(np.int32) + ulps).view(F)
    got = jax.jit(correct_quotient)(jnp.asarray(a), jnp.asarray(d),
                                    jnp.asarray(q))
    assert np.array_equal(np.asarray(got), want)


@pytest.mark.parametrize("v", [1_945_600.0, 1_126_400.0])
def test_a_over_a_is_one(v):
    """The two values whose a / a the chip rounded to 1.0000001."""
    a = np.array([v], F)
    assert correct_quotient(a, a, np.array([1.0000001], F))[0] == 1.0
    assert np.asarray(div_rn(jnp.asarray(a), jnp.asarray(a)))[0] == 1.0


def test_equal_shares_tie():
    """3/6 and 4/8 of one dimension share 0.5, and two queues each at
    exactly its deserved in a dimension share 1.0; namespace keys are the
    IEEE quotients."""
    alloc = jnp.asarray([[3.0, 0.0], [4.0, 0.0], [1_945_600.0, 1.0],
                         [1_126_400.0, 2.0]], jnp.float32)
    des = jnp.asarray([[6.0, jnp.inf], [8.0, jnp.inf],
                       [1_945_600.0, 10.0], [1_126_400.0, 10.0]],
                      jnp.float32)
    share = np.asarray(queue_share(alloc, des))
    assert share[0] == share[1] == 0.5 and share[2] == share[3] == 1.0
    total = np.array([6.0, 8.0], F)
    weight = np.array([1.0, 1.0, 2.0, 3.0], F)
    ns = namespace_share(alloc, jnp.asarray(total), jnp.asarray(weight))
    want = np.max(np.asarray(alloc) / total, axis=-1) / weight
    assert np.array_equal(np.asarray(ns), want)
