"""The one traffic generator: a mix file of parameters in, requests out.

A mix (``bench/traffic/<mix>.json``) gives the loop (``open``: arrivals on
a schedule, ``closed``: a fixed number of clients that each send their next
request when the last one completes), the length distributions, and for an
open loop the load as a share of the cell's measured knee
(``bench/rates/<config>.<mix>.json``).

Every run gets one schedule of prompt lengths, output lengths and
inter-arrival gaps; the run's seed draws the token ids (and, in the
harness, the weights).  Each of the three is the ``n`` mid-quantiles of
its distribution, cut into ``block`` strata: block ``j`` of ``block``
consecutive requests holds one draw of every stratum (a fixed van der
Corput order over the blocks), in an order within the block dealt by the
fixed ``SCHEDULE_SEED``.  So every stretch of a run holds the whole
spread of each distribution.  The schedule is fixed because a window
completes only some tens of requests: when the run's seed dealt the
order, the order decided how many long requests fell in the window, and
the metrics moved between seeds by several times their spread between
two runs of one seed.
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np

HERE = Path(__file__).resolve().parent
SCHEDULE_SEED = 0            # deals the one schedule every run gets


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: float = 0.0            # open loop: seconds after the schedule starts
    client: int = -1            # closed loop: which client sends it


def load_mix(name: str) -> dict:
    return json.loads((HERE / "traffic" / f"{name}.json").read_text())


def quantiles(dist: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution, clipped, as ints."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(x) for x in u])
        vals = dist["median"] * np.exp(dist["sigma"] * z)
    elif dist["dist"] == "fixed":
        vals = np.full(n, float(dist["value"]))
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(n: int) -> np.ndarray:
    """Mid-quantiles of the unit exponential (mean 1 up to the tail cut)."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u)


def van_der_corput(m: int) -> np.ndarray:
    """A fixed order of ``range(m)`` that spreads any prefix over the
    range (bit reversal)."""
    bits = max(1, (m - 1).bit_length())
    keys = [int(f"{j:0{bits}b}"[::-1], 2) for j in range(m)]
    return np.argsort(keys, kind="stable")


def stratified(values: np.ndarray, block: int, rng) -> np.ndarray:
    """Deal ``values`` so that each run of ``block`` consecutive entries
    holds one value of every stratum, in the order ``rng`` deals."""
    m = len(values) // block
    strata = np.sort(values)[:m * block].reshape(block, m)
    pick = van_der_corput(m)
    out = np.empty(m * block, values.dtype)
    for j in range(m):
        out[j * block:(j + 1) * block] = \
            strata[:, pick[j]][rng.permutation(block)]
    return out


def count_for(mix: dict, seconds: float, rate: float | None) -> int:
    """How many requests a run of ``seconds`` (warm-up included) can use,
    rounded up to whole blocks."""
    block = mix["block"]
    if mix["loop"] == "open":
        n = math.ceil(rate * seconds * 1.25) + 2 * block
    else:
        # a client can finish at most one request per prompt chunk + token
        n = mix["clients"] * (math.ceil(seconds) + 8)
        n = min(n, mix["clients"] * mix.get("max_per_client", 64))
    return -(-n // block) * block


def make(mix: dict, seed: int, n: int, vocab: int,
         rate: float | None = None) -> list[Request]:
    """``n`` requests for ``seed`` (``n`` a multiple of the mix's block):
    the one schedule of lengths and gaps, token ids drawn from ``seed``."""
    rng = np.random.default_rng(int(seed))
    sched = np.random.default_rng(SCHEDULE_SEED)
    block = mix["block"]
    plen = stratified(quantiles(mix["prompt_tokens"], n), block, sched)
    olen = stratified(quantiles(mix["output_tokens"], n), block, sched)
    if mix["loop"] == "open":
        gaps = stratified(exp_gaps(n), block, sched) / rate
        due = np.cumsum(gaps) - gaps[0]
    else:
        due = np.zeros(n)
    clients = mix.get("clients", 0)
    reqs = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plen[i]), dtype=np.int32)
        reqs.append(Request(index=i, prompt=prompt, max_new=int(olen[i]),
                            due=float(due[i]),
                            client=i % clients if clients else -1))
    return reqs
