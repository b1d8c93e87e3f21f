"""Interconnect traffic of staging's all-gather, computed from sizes alone.

Each of ``chips`` chips holds 1/``chips`` of an array and ends with all of
it, so each has to receive at least the part it did not hold, whatever
algorithm or piece size gathers it. Bytes that an implementation moves
beyond that (a ring's forwarding, copies into the replica) are not
counted, so the share of the roofline reads the same work whatever
gathers it.
"""
from __future__ import annotations


def all_gather_min_bytes_per_chip(nbytes: int, chips: int) -> int:
    """Least bytes one chip receives when ``nbytes`` in equal shares on
    ``chips`` chips become a replica on each."""
    return nbytes * (chips - 1) // chips
