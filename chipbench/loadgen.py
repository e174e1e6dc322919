"""The one traffic generator: turns a traffic mix file
(``chipbench/traffic/<mix>.json``) into the queries of one run.

A mix is data only. Its keys:

* ``loop``: ``"closed"`` (one client sends its next query when the last
  one has answered) or ``"open"`` (queries arrive on a schedule whether
  or not the last one has answered).
* ``rate_per_s`` (open loop): the offered rate. Arrival gaps are the
  quantiles of an exponential distribution at that rate, scaled to fill
  the window, and shuffled by the seed: every seed offers the same number
  of queries with the same set of gaps, in another order (Poisson-like
  arrivals with no seed-to-seed change in the amount of work).
* ``bursts`` (open loop, optional): ``{"period_s": p, "on_share": f}``:
  queries arrive only in the first ``f`` of every ``p`` seconds, at
  ``rate_per_s / f`` there, so the mean rate and the work stay the same
  (on/off bursts).
* ``params``: one entry per query parameter, each a distribution:

  - ``{"dist": "zipf", "items": n, "theta": t}``: an item of ``0..n-1``,
    rank ``k`` drawn with probability proportional to ``1/k**t`` (YCSB's
    zipfian), ranks mapped to items by a permutation drawn from the seed.
    ``items`` may name a key of the configuration instead of a number.
  - ``{"dist": "ordered_pair", "low": a, "high": b}``: two distinct
    integers of ``[a, b]``, drawn uniformly, smaller first.
  - ``{"dist": "choice", "values": [...], "weights": [...]}``: one of
    ``values`` (numbers or strings, such as the kind of an operation),
    drawn with probabilities proportional to ``weights``.

Warm-up visits every item of each ``zipf`` parameter twice, and sends at
least two queries in all, so that the window meets only warm data: the
program stages an operand into its leaf cache on the first flush that
reads it and makes it resident on the device on the second.
Seeds are whole numbers of any size; every stream is derived from the
seed and a fixed purpose number, so the same seed gives the same queries.
"""

from __future__ import annotations

import itertools

import numpy as np

DATA, PARAMS, ARRIVALS, WARMUP, ITEMS = range(5)
_CHUNK = 1024


def rng(seed: int, purpose: int) -> np.random.Generator:
    """The generator of one purpose (data, params, arrivals, warm-up,
    item order) for ``seed``; any whole number is a valid seed."""
    return np.random.default_rng(
        np.random.SeedSequence([abs(int(seed)), int(seed < 0), purpose]))


class _Zipf:
    def __init__(self, spec: dict, config: dict, seed: int):
        items = spec["items"]
        self.n = int(config[items] if isinstance(items, str) else items)
        if self.n < 1:
            raise ValueError(f"zipf needs at least one item, got {self.n}")
        w = 1.0 / np.arange(1, self.n + 1, dtype=np.float64) \
            ** float(spec["theta"])
        self.p = w / w.sum()
        self.perm = rng(seed, ITEMS).permutation(self.n)

    def draw(self, g: np.random.Generator, k: int) -> list:
        return [int(x) for x in self.perm[g.choice(self.n, k, p=self.p)]]

    def warm(self, g: np.random.Generator) -> list:
        return list(range(self.n)) * 2


class _OrderedPair:
    def __init__(self, spec: dict, config: dict, seed: int):
        self.low, self.high = int(spec["low"]), int(spec["high"])
        if self.high <= self.low:
            raise ValueError("ordered_pair needs low < high")

    def draw(self, g: np.random.Generator, k: int) -> list:
        span = self.high - self.low + 1
        a = g.integers(0, span, k)
        # b != a: draw from the span minus one and step over a.
        b = g.integers(0, span - 1, k)
        b = b + (b >= a)
        lo, hi = np.minimum(a, b) + self.low, np.maximum(a, b) + self.low
        return [(int(x), int(y)) for x, y in zip(lo, hi)]

    def warm(self, g: np.random.Generator) -> list:
        return self.draw(g, 2)


class _Choice:
    def __init__(self, spec: dict, config: dict, seed: int):
        self.values = list(spec["values"])
        w = np.asarray(spec["weights"], dtype=np.float64)
        if len(w) != len(self.values) or not len(w) or (w < 0).any() \
                or w.sum() <= 0:
            raise ValueError("choice needs one non-negative weight per "
                             "value, not all zero")
        self.p = w / w.sum()

    def draw(self, g: np.random.Generator, k: int) -> list:
        return [self.values[i] for i in g.choice(len(self.values), k,
                                                 p=self.p)]

    def warm(self, g: np.random.Generator) -> list:
        return [v for v, p in zip(self.values, self.p) if p > 0] * 2


_DISTS = {"zipf": _Zipf, "ordered_pair": _OrderedPair, "choice": _Choice}


class Traffic:
    """One traffic mix bound to a configuration and a seed."""

    def __init__(self, spec: dict, config: dict, seed: int):
        if spec.get("loop") not in ("closed", "open"):
            raise ValueError(f"traffic loop must be 'closed' or 'open', "
                             f"got {spec.get('loop')!r}")
        self.spec = spec
        self.seed = seed
        self.loop = spec["loop"]
        self.params = {}
        for name, p in spec.get("params", {}).items():
            try:
                cls = _DISTS[p["dist"]]
            except KeyError:
                raise ValueError(f"parameter {name!r}: unknown dist "
                                 f"{p.get('dist')!r}") from None
            self.params[name] = cls(p, config, seed)

    def _draw(self, g: np.random.Generator, k: int) -> list[dict]:
        cols = {name: d.draw(g, k) for name, d in self.params.items()}
        return [{name: col[i] for name, col in cols.items()}
                for i in range(k)]

    def warmup(self) -> list[dict]:
        """Queries that touch every item and shape the window will, each
        at least twice."""
        g = rng(self.seed, WARMUP)
        cols = {name: d.warm(g) for name, d in self.params.items()}
        n = max([2] + [len(c) for c in cols.values()])
        visits = [{name: col[i % len(col)] for name, col in cols.items()}
                  for i in range(n)]
        return visits

    def stream(self):
        """Closed loop: an endless iterator of query parameters."""
        g = rng(self.seed, PARAMS)
        while True:
            yield from self._draw(g, _CHUNK)

    def first(self, n: int) -> list[dict]:
        return list(itertools.islice(self.stream(), n))

    def arrivals(self, seconds: float, rate: float | None = None
                 ) -> list[tuple[float, dict]]:
        """Open loop: ``(due second, params)`` for every query offered in
        a window of ``seconds``, the first due at 0."""
        rate = float(self.spec["rate_per_s"] if rate is None else rate)
        n = max(1, int(round(rate * seconds)))
        bursts = self.spec.get("bursts")
        share = float(bursts["on_share"]) if bursts else 1.0
        if not 0 < share <= 1:
            raise ValueError(f"bursts on_share must be in (0, 1], got "
                             f"{share}")
        on_s = seconds * share      # arrivals fill the on time only
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q)        # exponential quantiles, then scaled
        gaps *= on_s / gaps.sum()
        gaps = rng(self.seed, ARRIVALS).permutation(gaps)
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        if bursts:  # on time to wall time: skip the off part of each period
            on_p = float(bursts["period_s"]) * share
            due = due + np.floor(due / on_p) * (float(bursts["period_s"])
                                                - on_p)
        return list(zip(due.tolist(), self._draw(rng(self.seed, PARAMS), n)))
