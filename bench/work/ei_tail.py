"""The EI tail's work: what any correct implementation must compute and
read for one chunk dispatch, from the rows still searching and their
observed slots t (not the capacity B).

Per row and candidate, with t observations and d features:

  * squared distances to the t observed points: 3 t d (difference,
    square, sum);
  * Matérn-5/2 of each: 8 t (sqrt, scale, square, third, two adds, exp,
    product), each transcendental counted as one;
  * the posterior mean: 2 t (multiply-add with alpha);
  * the variance by forward substitution against the (t, t) factor:
    t² + 2 t (t(t-1) multiply-subtracts, t divisions, t squares summed);
  * the rest, a constant 16: 1 - sum, sqrt, de-standardizing mean (2) and
    std (1), improvement, z, the normal CDF (one), the density (3), EI (3),
    the clamp and the running argmax.

The dispatch span gives each dispatch's rows R and slots S = Σ t, not the
t of each row, so Σ t² is taken at its least, S² / R.  Every term is thus
a lower bound, and the share of a roofline read from them cannot pass
100 % unless a peak is wrong.

Bytes per row: the (d, n) float32 encoding, the candidate mask (one byte
per candidate) and the float32 cost table.
"""

from __future__ import annotations


def flops(rows: int, slots: int, n: int, d: int) -> float:
    if rows <= 0:
        return 0.0
    return float(n) * (slots * slots / rows + (3 * d + 12) * slots
                       + 16 * rows)


def bytes_read(rows: int, n: int, d: int) -> float:
    return float(rows) * n * (4 * d + 1 + 4)
