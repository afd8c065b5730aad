"""Open-loop request schedules for the query mixes, from the seed alone.

Every seed gets the same number of requests of each (query, mode) kind,
``round(rate * seconds)`` in all, and the same gaps between arrivals:
the quantiles of the exponential distribution of a Poisson process at
the rate, scaled to fill the window.  The seed decides the order of the
requests and of the gaps, and the client ids.  So seeds change the order
of the work, not its size or its burstiness.
"""
from __future__ import annotations

import numpy as np


def kinds(traffic: dict) -> list:
    """[(query index, mode, share)] of the mix."""
    total = sum(q["weight"] for q in traffic["queries"])
    return [(i, mode, q["weight"] / total * share)
            for i, q in enumerate(traffic["queries"])
            for mode, share in sorted(traffic["modes"].items())]


def build(traffic: dict, seed: int, seconds: float) -> list:
    """[(due offset s, client id, query index, mode)], by due time."""
    n = int(round(traffic["rate_per_s"] * seconds))
    ks = kinds(traffic)
    want = np.asarray([s for _, _, s in ks]) * n
    count = np.floor(want).astype(int)
    # largest remainders take the requests that rounding left over
    for j in np.argsort(-(want - count), kind="stable")[:n - count.sum()]:
        count[j] += 1
    rng = np.random.default_rng([int(seed), 2])
    order = rng.permutation(np.repeat(np.arange(len(ks)), count))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    due = np.cumsum(rng.permutation(gaps))
    due = (due - due[0]) * (seconds * (n - 1) / n) / max(due[-1] - due[0],
                                                         1e-12)
    client = rng.integers(0, traffic["clients"], size=n)
    return [(float(d), f"c{int(c):03d}", ks[k][0], ks[k][1])
            for d, c, k in zip(due, client, order)]
