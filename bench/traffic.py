"""The one traffic generator: a traffic mix is a data file of parameters
(``bench/traffic/<mix>.json``); this module turns it and a run's seed
into requests.

A mix file holds:

- ``loop``: ``closed`` (a fixed number of requests outstanding; the next
  is sent when one completes) or ``open`` (requests due on a schedule,
  sent whether or not earlier ones completed);
- ``kinds``: weights of ``enc`` and ``dec`` requests, e.g. 10 and 1;
- ``outstanding`` (closed loop) or ``rate_per_s`` and ``arrival_seed``
  (open loop, Poisson arrivals);
- ``service``: the client service's batching settings (``buckets``,
  ``fire_mode``, ``max_wait_s``);
- ``message_pool`` and ``decrypt_pool``: how many distinct messages and
  two-limb ciphertexts a run draws from its seed;
- ``check``: how many completed requests of each kind the run compares
  with the reference, and ``check_strata``: how many strata the sample
  is spread over evenly, request i in stratum i % strata (in a closed
  loop of full buckets, i % bucket is the request's row in its job).

Every seed gets the same work in another order: the open loop's
inter-arrival gaps are one fixed set drawn from ``arrival_seed`` and
shuffled by the run's seed, and the share of each kind is fixed.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

KINDS = ("enc", "dec")


def load(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("closed", "open"):
        raise ValueError(f"{path}: loop must be 'closed' or 'open'")
    weights = mix.get("kinds", {})
    if set(weights) - set(KINDS) or not any(weights.values()):
        raise ValueError(f"{path}: kinds must weight 'enc' and/or 'dec'")
    if mix["loop"] == "closed" and int(mix.get("outstanding", 0)) < 1:
        raise ValueError(f"{path}: a closed loop needs outstanding >= 1")
    if mix["loop"] == "open" and float(mix.get("rate_per_s", 0)) <= 0:
        raise ValueError(f"{path}: an open loop needs rate_per_s > 0")
    mix["name"] = os.path.splitext(os.path.basename(path))[0]
    return mix


def rng(seed: int, purpose: str) -> np.random.Generator:
    """An independent stream per purpose, from a seed of any size."""
    words = [int(seed) >> (32 * i) & 0xFFFFFFFF for i in range(3)]
    return np.random.default_rng(
        [*words, sum(map(ord, purpose)), len(purpose)])


def kinds_used(mix: dict) -> list[str]:
    return [k for k in KINDS if mix["kinds"].get(k, 0) > 0]


def messages(seed: int, count: int, n_slots: int,
             purpose: str = "messages") -> np.ndarray:
    """``count`` complex messages, uniform in [-1, 1]^2 on every slot."""
    g = rng(seed, purpose)
    shape = (count, n_slots)
    return g.uniform(-1, 1, shape) + 1j * g.uniform(-1, 1, shape)


@dataclasses.dataclass(frozen=True)
class Schedule:
    """What request i is: its kind, the pool entry it carries and, in an
    open loop, when it is due (seconds from the window's start)."""

    kinds: np.ndarray               # one per request of the pattern
    due: np.ndarray | None          # open loop: (n,) seconds, rising
    message_pool: int
    decrypt_pool: int

    def kind(self, i: int) -> str:
        return str(self.kinds[i % len(self.kinds)])

    def entry(self, i: int) -> int:
        """Index into the message or ciphertext pool of request i."""
        pool = self.message_pool if self.kind(i) == "enc" \
            else self.decrypt_pool
        return i % pool

    def __len__(self) -> int:
        return len(self.due) if self.due is not None else len(self.kinds)


def schedule(mix: dict, seed: int, seconds: float) -> Schedule:
    weights = [int(mix["kinds"].get(k, 0)) for k in KINDS]
    pools = (int(mix.get("message_pool", 1)), int(mix.get("decrypt_pool", 1)))
    g = rng(seed, "schedule")
    if mix["loop"] == "closed":
        pattern = np.repeat(np.array(KINDS), weights)
        return Schedule(g.permutation(pattern), None, *pools)
    gaps = rng(int(mix["arrival_seed"]), "arrivals").exponential(
        1.0 / float(mix["rate_per_s"]), size=int(4 * seconds
                                                  * float(mix["rate_per_s"])
                                                  + 64))
    gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), seconds))]
    due = np.cumsum(g.permutation(gaps))
    n = len(due)
    n_dec = int(round(n * weights[1] / sum(weights)))
    kinds = np.array(["enc"] * (n - n_dec) + ["dec"] * n_dec)
    return Schedule(g.permutation(kinds), due, *pools)


class Reservoir:
    """A uniform sample of ``size`` items from a stream of unknown length,
    drawn from the seed (Algorithm R): the same stream and seed keep the
    same items. With ``strata`` > 1 the sample is spread evenly over the
    strata, each item offered with its key and kept in stratum
    key % strata, a uniform sample within each."""

    def __init__(self, size: int, seed: int, purpose: str, strata: int = 1):
        self.size = int(size)
        strata = max(1, min(int(strata), self.size or 1))
        self._caps = [self.size // strata + (s < self.size % strata)
                      for s in range(strata)]
        self._parts: list[list] = [[] for _ in range(strata)]
        self._seen = [0] * strata
        self._rng = rng(seed, purpose)

    @property
    def items(self) -> list:
        return [item for part in self._parts for item in part]

    def offer(self, item, key: int = 0) -> None:
        s = key % len(self._parts)
        part, cap = self._parts[s], self._caps[s]
        if len(part) < cap:
            part.append(item)
        else:
            j = int(self._rng.integers(0, self._seen[s] + 1))
            if j < cap:
                part[j] = item
        self._seen[s] += 1
