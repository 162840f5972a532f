"""The least HBM bytes the sign optimizer's update moves per chip per step.

Per parameter: read the gradient at the parameter's dtype, read and
write the momentum at its dtype, read and write the parameter. With M
voting replicas the wire adds, per parameter:

* ``allgather_1bit``: 1/8 byte written by the pack, (M - 1)/8 written by
  the gather, and M/8 read by the tally;
* ``psum_int8``: 1 byte written by the pack (the int8 count), 1 written
  by the all-reduce, and 1 read by the unpack (the tally is the identity).

Nothing else (the forward and backward's own traffic) is counted.
"""
from __future__ import annotations

from typing import Iterable, Optional, Tuple


def wire_bytes(wire: Optional[str], replicas: int) -> float:
    """The vote's bytes per parameter on `replicas` replicas by `wire`."""
    if replicas == 1:
        return 0.0
    if wire == "allgather_1bit":
        return (1 + (replicas - 1) + replicas) / 8.0
    if wire == "psum_int8":
        return 3.0
    raise ValueError(f"no byte count of the vote on the wire {wire!r}")


def optimizer_bytes(leaves: Iterable[Tuple[int, int]], momentum_bytes: int,
                    replicas: int, wire: Optional[str]) -> float:
    """`leaves`: (element count, parameter bytes per element) per leaf;
    `wire`: the vote's wire (unused on one replica)."""
    per_vote = wire_bytes(wire, replicas)
    total = 0.0
    for n, p_bytes in leaves:
        total += n * (p_bytes + 2 * momentum_bytes + 2 * p_bytes + per_vote)
    return total
