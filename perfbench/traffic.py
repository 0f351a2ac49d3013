"""The one traffic generator: a mix file of parameters -> seeded requests.

Every seed gets the same requests' sizes: lengths are the stratified
quantiles ``(i + 0.5) / n`` of the stated distributions, prompt and output
lengths are paired by one fixed permutation, and which requests are greedy
follows from their lengths alone.  The seed draws the prompt tokens, each
request's sampling seed and the order:

* closed loop: the requests are dealt into rounds, one request per client
  in each, every round spanning the lengths; client ``c`` sends requests
  ``c, c + clients, ...`` in turn.  A client's script is fixed, the seed
  only relabels the clients, so every seed offers the same work at the
  same times;
* open loop: Poisson arrivals at ``rate_per_s``, the same set of gaps for
  every seed in another order, timed from when each request is due.

Mix keys: ``loop`` ("closed", with ``clients_per_slot`` clients per batch
slot and a ``pool`` of requests; or "open", with ``rate_per_s``),
``prompt_len`` and ``output_len`` (``{"dist": "uniform", "min", "max"}`` or
``{"dist": "lognormal", "median", "sigma", "min", "max"}``),
``greedy_share`` (greedy requests, which the correctness comparison reads;
the rest sample at ``temperature`` with the scheduler's ``top_k``).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Optional

import numpy as np

#: seed of the one permutation that pairs prompt with output lengths
PAIRING_SEED = 0


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray
    max_new: int
    greedy: bool
    seed: int
    arrival: Optional[float] = None      # seconds after the window opens


def quantiles(spec: dict, n: int) -> list[int]:
    """``n`` stratified lengths of a length distribution, ascending."""
    lo, hi = spec["min"], spec["max"]
    out = []
    for i in range(n):
        u = (i + 0.5) / n
        if spec["dist"] == "uniform":
            x = lo + math.floor(u * (hi - lo + 1))
        elif spec["dist"] == "lognormal":
            x = round(spec["median"] * math.exp(
                spec["sigma"] * NormalDist().inv_cdf(u)))
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(int(min(max(x, lo), hi)))
    return out


def request_count(mix: dict, seconds: float) -> int:
    if mix["loop"] == "closed":
        return mix["pool"]
    if mix["loop"] == "open":
        return max(1, round(mix["rate_per_s"] * seconds))
    raise ValueError(f"unknown loop {mix['loop']!r}")


def _every(mix: dict) -> int:
    """Every how many requests one is greedy (0: none)."""
    share = mix["greedy_share"]
    return max(1, round(1 / share)) if share else 0


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             clients: Optional[int] = None) -> list:
    """The run's requests, in the order they are sent (a closed loop's
    ``clients`` take them in turn, as above)."""
    rng = np.random.default_rng(int(seed) % 2**64)
    n = request_count(mix, seconds)
    plen = np.asarray(quantiles(mix["prompt_len"], n))
    olen = np.asarray(quantiles(mix["output_len"], n))[
        np.random.default_rng(PAIRING_SEED).permutation(n)]
    rank = np.argsort(np.argsort(plen + olen, kind="stable"), kind="stable")
    every = _every(mix)
    arrivals = None
    if mix["loop"] == "closed":
        if not clients or n % clients:
            raise ValueError(f"a pool of {n} cannot be dealt to {clients} "
                             "clients in whole rounds")
        rounds = n // clients
        # rank k -> round k % rounds, k // rounds-th shortest there; the
        # client of that place turns by one each round
        rnd, j = rank % rounds, rank // rounds
        client = (j + rnd) % clients
        greedy = (client % every == 1 % every) if every else np.zeros(n, bool)
        label = rng.permutation(clients)
        order = np.argsort(rnd * clients + label[client])
    else:
        # the longest request and every ``every``-th below it by length
        greedy = ((n - 1 - rank) % every == 0) if every else np.zeros(n, bool)
        order = rng.permutation(n)
        gaps = np.asarray([-math.log(1 - (i + 0.5) / n) / mix["rate_per_s"]
                           for i in range(n)])
        gaps = rng.permutation(gaps) * (seconds / gaps.sum())
        arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    out = []
    for k, j in enumerate(order):
        out.append(Request(
            index=k,
            prompt=rng.integers(0, vocab, int(plen[j])).astype(np.int32),
            max_new=int(olen[j]), greedy=bool(greedy[j]),
            seed=int(rng.integers(0, 2**31)),
            arrival=None if arrivals is None else float(arrivals[k])))
    return out
