"""Float32 division rounded as IEEE division rounds it.

The TPU's f32 divide (XLA and Mosaic alike) can land an ulp away from the
correctly rounded quotient: ``a / a`` came out 1.0000001 for a = 1,945,600
and 1,126,400. Where a quotient is only compared with another, as queue and
namespace shares are when the kernels pick the next job, two shares that
IEEE floats make equal (3 / 6 and 4 / 8, or a / a and b / b) must come out
equal on the chip too, or the pick breaks their tie by rounding.
:func:`div_rn` corrects the device's quotient by its exact residual, with
multiplies, adds and one more divide only, so the same code runs under XLA
and inside a Pallas kernel.
"""

from __future__ import annotations

_SPLIT = 4097.0          # 2**12 + 1: Veltkamp's split of a 24-bit mantissa


def _split(x):
    """``x`` as hi + lo, each of at most 12 significant bits, exactly."""
    c = _SPLIT * x
    hi = c - (c - x)
    return hi, x - hi


def correct_quotient(a, d, q):
    """``q``, a quotient of ``a / d`` a few ulps off at most, moved to the
    float nearest ``a / d``.

    ``p = q * d`` rounds; Dekker's product gives its rounding error ``e``
    exactly, so ``a - q * d = (a - p) - e``, where ``a - p`` is exact (p
    lies within a factor 2 of a). The true quotient is ``q`` plus that
    residual over ``d``, less than an ulp away, and the final add rounds it
    to nearest. The correction carries a relative error near 2**-23 of an
    ulp, so only a quotient that close to the midpoint of two floats could
    round the other way; IEEE division never lands on a midpoint.
    ``a`` and ``d`` are finite and ``d`` nonzero, as the callers' guards
    make them."""
    p = q * d
    qh, ql = _split(q)
    dh, dl = _split(d)
    e = ((qh * dh - p) + qh * dl + ql * dh) + ql * dl
    return q + ((a - p) - e) / d


def div_rn(a, d):
    """``a / d`` rounded to nearest, elementwise, on any backend."""
    return correct_quotient(a, d, a / d)
