"""What every family's operation counts share: the roofline of a call.

A family counts the operations and bytes of its own layers
(``bench/families/<family>/flops.py``): what the algorithm needs, not what
an implementation pads or recomputes, so a later kernel or layout changes
the time and never the count.
"""
from __future__ import annotations


def roofline_s(flops: float, nbytes: float, peaks: dict) -> float:
    """Least time the chip could take: the larger of the two bounds."""
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
