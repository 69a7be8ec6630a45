"""The one traffic generator: turns a mix file (``chipbench/traffic/
<mix>.json``) and a seed into the inputs of a run.

Every stream is drawn from the run's seed through ``derive``, so the
same seed gives the same inputs.  Counts are fixed by the mix and the
run's length, never drawn: seeds change which inputs come and in what
order, not how much work there is.

Serving mixes (``"kind": "serve"``):

* ``rate_per_s``: mean offered rate; a run of ``seconds`` offers
  ``round(rate_per_s * seconds)`` requests.
* ``arrivals``: ``"poisson"``, arrival times uniform over the window,
  i.e. a Poisson process given its count.
* ``repeats`` (optional): ``{"pool": P, "zipf_s": s}`` draws each
  request from ``P`` fresh sequences with rank-Zipf probability
  ``rank ** -s``; without it every request is a fresh sequence.
"""
from __future__ import annotations

import hashlib
import math
from typing import List, Sequence

import numpy as np

from . import gen


def derive(seed: int, *tags) -> int:
    """A 63-bit seed for one named stream of the run ``seed``."""
    text = "/".join([str(int(seed))] + [str(t) for t in tags])
    return int.from_bytes(hashlib.sha256(text.encode()).digest()[:8],
                          "big") >> 1


def count(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def arrival_times(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Sorted arrival times in ``[0, seconds)``."""
    n = count(mix, seconds)
    rng = np.random.default_rng(derive(seed, "arrivals"))
    kind = mix.get("arrivals", "poisson")
    if kind == "poisson":
        return np.sort(rng.uniform(0.0, seconds, n))
    raise ValueError(f"unknown arrival process {kind!r}")


def sequences(config: dict, n: int, seed: int) -> List[gen.Seq]:
    """``n`` distinct fresh sequences from the configuration's generator
    (its database sizes, the run's seed)."""
    out, seen, k = [], set(), 0
    while len(out) < n:
        want = n - len(out)
        for s in gen.database(config, derive(seed, "seqs", k),
                              want + want // 16 + 8):
            if s not in seen and len(out) < n:
                seen.add(s)
                out.append(s)
        k += 1
    return out


def requests(config: dict, mix: dict, n: int, seed: int
             ) -> List[gen.Seq]:
    """The ``n`` requests of a serving run, in arrival order."""
    rep = mix.get("repeats")
    if not rep:
        return sequences(config, n, derive(seed, "fresh"))
    pool = sequences(config, int(rep["pool"]), derive(seed, "pool"))
    p = np.arange(1, len(pool) + 1, dtype=np.float64) ** -float(rep["zipf_s"])
    p /= p.sum()
    rng = np.random.default_rng(derive(seed, "repeats"))
    return [pool[i] for i in rng.choice(len(pool), size=n, p=p)]


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile over all values: the smallest value at or
    above which ``q`` percent lie.  ``inf`` entries (failed, late past
    the end, inexact) take part as values larger than every finite
    one."""
    xs = sorted(values)
    if not xs:
        return math.inf
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return float(xs[k - 1])
