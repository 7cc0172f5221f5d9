"""Seeds of a run's traffic, read from data files.

A mix is a JSON object (``workloads/<cell>.json``, key ``traffic``) that the
cell's driver reads: population, generations, tile rows and, for drift, the
spread of link slowdowns and how often a node drops.  Every seed gets the
same work in its own order; these helpers derive each purpose's stream from
the run's ``--seed``, however large.
"""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A NumPy generator for one purpose of one run; any whole ``seed``,
    however large, is accepted."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def sub_seed(seed: int, i: int) -> int:
    """The ``i``-th 31-bit seed derived from ``seed`` (for program calls
    that take a seed of their own)."""
    return int(np.random.SeedSequence([int(seed), 7919, i])
               .generate_state(1)[0] % (2 ** 31))
