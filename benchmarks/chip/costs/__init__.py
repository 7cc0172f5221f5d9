"""Operations and bytes of the measured work, from shapes only.

Each function counts what the algorithm needs for one call at the given
sizes, never what a particular implementation happens to do, so a roofline
share built on it reads the same whatever implements the work.  Counts are
lower bounds where the text says so; a share built on them can only read
low, never above 100%.

``roofline_s`` turns a count into the least time the chip could take.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Work:
    """Operations and bytes moved to or from device memory."""
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.ops * k, self.bytes * k)


def roofline_s(work: Work, peaks: Dict) -> float:
    """The larger of operations over peak rate and bytes over bandwidth."""
    return max(work.ops / peaks["ops_per_s"], work.bytes / peaks["hbm_bytes_per_s"])


# -- partition search ---------------------------------------------------------

def packed_domination(n: int, m: int) -> Work:
    """Constrained domination of every ordered pair of ``n`` rows with ``m``
    objectives, bit-packed: per pair ``m`` "<=" and ``m`` "<" compares and one
    violation compare; the rows read once, the ``n * n / 8`` bytes of packed
    bits written once."""
    return Work(ops=float(n) * n * (2 * m + 1),
                bytes=n * (m + 1) * 4.0 + n * n / 8.0)


def domination_counts(n: int, m: int) -> Work:
    """Count of alive constrained dominators of each of ``n`` rows: the
    pair compares of :func:`packed_domination` plus one add per pair; the
    rows and the alive mask read once, ``n`` int32 counts written."""
    return Work(ops=float(n) * n * (2 * m + 2),
                bytes=n * (m + 2) * 4.0 + n * 4.0)


def evaluation(rows: int, platforms: int, links: int) -> Work:
    """Objectives of ``rows`` cut vectors from prefix-sum tables: per row
    and platform two latency/energy prefix reads and the Def.-3 memory
    (parameter prefix pair and two range-max reads), per link one element
    count; about ten operations per gathered value.  All values float32."""
    gathers = rows * (platforms * 8 + links)
    return Work(ops=10.0 * gathers, bytes=4.0 * gathers)


def search_generation(pop: int, m: int, platforms: int, links: int) -> Work:
    """One NSGA-II generation at population ``pop``: the offspring's
    evaluation and the packed domination of the combined ``2 * pop``
    population, whose bits the front peeling reads at least once.  The
    peeling's further passes, crowding and the sorts are left out, so this
    is a lower bound."""
    n = 2 * pop
    rank = packed_domination(n, m)
    return (rank + Work(0.0, n * n / 8.0)
            + evaluation(pop, platforms, links))
