"""Shares of a peak, from counts of the work itself.

A per-layer metric divides a count from shapes (the per-model training
counts sit beside each configuration,
``bench/configs/<model>.py:train_flops``) by a time measured on the chip.
"""

from __future__ import annotations


def share_pct(part: float, whole: float) -> float | None:
    """``part / whole`` in percent; None when there is nothing to divide
    (a share is never reported as 0 for want of a reading)."""
    if not whole or whole <= 0 or part is None or part <= 0:
        return None
    return 100.0 * part / whole
