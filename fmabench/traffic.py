"""The one general traffic generator: a mix is a data file of parameters.

Two kinds (``fmabench/traffic/<mix>.json``, key ``kind``):

``closed``  N clients, each sending its next request when its last one
            completes (offline batch jobs);
``open``    arrivals on a schedule fixed before the window, whether or not
            earlier requests have finished (independent users).

Steadiness rule (the builder's contract): the seed never changes the
*work*. Lengths and arrival gaps are a fixed, stratified set — the
quantiles of the stated distribution, not draws from it — and the seed
only shuffles their order and draws the token ids. So every seed offers
the same tokens at the same mean rate. Sound arithmetic copied from
``llm_d_fast_model_actuation_tpu/benchmark/fleet.py`` (seeded stdlib
``random``, nearest-rank percentile); nothing is imported from it.
"""

from __future__ import annotations

import hashlib
import json
import math
import random
from statistics import NormalDist
from typing import Any, Dict, Iterator, List, Sequence


def stratified_lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` lengths: the (i + 0.5) / n quantiles of the distribution,
    clipped. ``{"dist": "fixed", "value": v}`` or ``{"dist": "lognormal",
    "median": m, "sigma": s, "min": a, "max": b}``."""
    if spec["dist"] == "fixed":
        return [int(spec["value"])] * n
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    mu, sigma = math.log(spec["median"]), float(spec["sigma"])
    nd = NormalDist()
    out = []
    for i in range(n):
        x = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        out.append(int(min(spec["max"], max(spec["min"], round(x)))))
    return out


def stratified_gaps(rate_rps: float, n: int) -> List[float]:
    """``n`` inter-arrival gaps of a Poisson process at ``rate_rps``: the
    exponential distribution's stratified quantiles, rescaled so that they
    sum to exactly n / rate (the clipped top quantile would otherwise
    shorten the mean)."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = (n / rate_rps) / sum(raw)
    return [g * scale for g in raw]


class Shapes:
    """An endless, seeded stream of (prompt_len, max_tokens) drawn epoch by
    epoch from the mix's fixed pool: every epoch is the whole pool in a new
    order, so any long stretch holds the same work whatever the seed."""

    def __init__(
        self, mix: Dict[str, Any], seed: int, stream: str, pool: int = 0
    ) -> None:
        n = int(mix.get("pool") or pool)
        prompts = stratified_lengths(mix["prompt_len"], n)
        outputs = stratified_lengths(mix["output_len"], n)
        # pair prompt and output lengths independently, by a shuffle that
        # is fixed and does not belong to the run
        random.Random("0:pair").shuffle(outputs)
        self.pool = list(zip(prompts, outputs))
        self._rng = random.Random(f"{seed}:{stream}:order")

    def __iter__(self) -> Iterator[tuple]:
        while True:
            epoch = list(self.pool)
            self._rng.shuffle(epoch)
            yield from epoch


class Prompts:
    """Seeded token ids, uniform over [1, vocab): no shared prefixes."""

    def __init__(self, vocab: int, seed: int, stream: str) -> None:
        self._ids = range(1, vocab)
        self._rng = random.Random(f"{seed}:{stream}:ids")

    def draw(self, n: int) -> List[int]:
        return self._rng.choices(self._ids, k=n)


def requests_stream(
    mix: Dict[str, Any], vocab: int, seed: int, stream: str, pool: int = 0
) -> Iterator[Dict[str, Any]]:
    """Endless requests of the mix: ``{"prompt": ids, "max_tokens": n}``.
    ``stream`` names an independent substream ("warmup", "window"); ``pool``
    is the pool's size where the mix does not fix one."""
    prompts = Prompts(vocab, seed, stream)
    for plen, out in Shapes(mix, seed, stream, pool):
        yield {"prompt": prompts.draw(plen), "max_tokens": out}


def open_schedule(
    mix: Dict[str, Any], vocab: int, seed: int, seconds: float,
    warm_s: float = 0.0,
) -> tuple:
    """The open loop's whole schedule: ``(warm-up rows, window rows)``, each
    row a request with its due time ``t_s`` from the start of its stretch.

    The window holds rate x seconds arrivals: the stratified exponential
    gaps and, where the mix fixes no ``pool``, exactly as many stratified
    shapes, so every window holds every gap and every shape once. Gaps and
    shapes are laid on a circle in a fixed order; the run's seed chooses
    where the circle is cut, and draws the ids. So two seeds meet the same bursts of the same requests,
    begun at another point — an order that differs from seed to seed moved
    the tail of the token gap by 4-5% (PERF.md section 2). The warm-up
    stretch is the arc that ends at the cut."""
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    order = random.Random("0:circle")
    gaps = stratified_gaps(rate, n)
    order.shuffle(gaps)
    shapes = list(Shapes(mix, 0, "", pool=n).pool)
    order.shuffle(shapes)
    cut = random.Random(f"{seed}:cut").randrange(n)

    def rows(indices, stream, t0):
        prompts, out, t = Prompts(vocab, seed, stream), [], t0
        for j, i in enumerate(indices):
            if j:
                t += gaps[i % n]
            plen, tokens = shapes[i % len(shapes)]
            out.append({"prompt": prompts.draw(plen), "max_tokens": tokens,
                        "t_s": round(t, 6)})
        return out

    # the first arrival is due at the open, the others after their gaps:
    # exactly n arrivals, the last one due before the close
    window = rows(range(cut, cut + n), "window", 0.0)
    back, span = [], 0.0
    i = cut
    while span + gaps[i % n] < warm_s:
        span += gaps[i % n]  # the gap that leads to arrival i
        i -= 1
        back.append(i)
    warm = rows(list(reversed(back)), "warmup", warm_s - span) if back else []
    return warm, window


def digest(requests: Sequence[Dict[str, Any]]) -> str:
    """sha256 of a list of requests: two same-seed runs agree on it."""
    h = hashlib.sha256()
    for r in requests:
        h.update(json.dumps(r, sort_keys=True).encode())
    return h.hexdigest()


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile, q in [0, 100]."""
    if not values:
        raise ValueError("percentile of nothing")
    xs = sorted(values)
    rank = min(len(xs), max(1, math.ceil(q / 100.0 * len(xs))))
    return xs[rank - 1]
