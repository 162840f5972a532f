"""The least HBM bytes the sign optimizer's update moves per chip per step.

Per parameter: read the gradient at the parameter's dtype, read and
write the momentum at its dtype, read and write the parameter. With M
voting replicas, the 1-bit wire adds per parameter: 1/8 byte written by
the pack, (M - 1)/8 written by the gather, and M/8 read by the tally.
Nothing else (the forward and backward's own traffic) is counted.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def optimizer_bytes(leaves: Iterable[Tuple[int, int]], momentum_bytes: int,
                    replicas: int) -> float:
    """`leaves`: (element count, parameter bytes per element) per leaf."""
    total = 0.0
    for n, p_bytes in leaves:
        total += n * (p_bytes + 2 * momentum_bytes + 2 * p_bytes)
        if replicas > 1:
            total += n * (1 + (replicas - 1) + replicas) / 8.0
    return total
