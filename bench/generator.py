"""Open-loop request schedules from a traffic mix and a seed.

A mix (``bench/traffic/<name>.json``) gives the length distributions and
the engine's serving shape; the cell (``bench/cells/<workload>.json``)
gives the arrival process and its rate. The schedule is cut into
segments (the lead before the window, the window, the tail after it).
Each segment's *set* of requests (prompt length, output length) and of
inter-arrival gaps depends only on those files and the segment's length:
it is drawn from a fixed generator. The run's seed reorders each set
within its segment, in runs of ``BLOCK``, and draws the prompt tokens,
so every seed offers the window the same work in another order.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

#: The fixed generator that draws the sets of sizes and gaps.
SIZE_SEED = 20240523


@dataclasses.dataclass(frozen=True)
class Request:
    due: float            # seconds after the schedule starts
    prompt: tuple[int, ...]
    out_len: int          # tokens to serve, the first one included


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` lengths from ``{"dist": "lognormal", "median", "sigma",
    "min", "max"}`` or ``{"dist": "fixed", "value"}``."""
    if spec["dist"] == "fixed":
        return np.full(n, int(spec["value"]))
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def gaps(arrival: dict, n: int, span_s: float,
         rng: np.random.Generator) -> np.ndarray:
    """``n`` inter-arrival gaps summing to ``span_s``.

    ``{"process": "poisson", "rate_per_s"}`` or ``{"process": "gamma",
    "rate_per_s", "cv"}`` (bursts: a coefficient of variation above 1).
    """
    if arrival["process"] == "poisson":
        g = rng.exponential(1.0, n)
    elif arrival["process"] == "gamma":
        shape = 1.0 / float(arrival["cv"]) ** 2
        g = rng.gamma(shape, 1.0 / shape, n)
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    return g * span_s / g.sum()


#: Requests are reordered only within runs of this many, so that every
#: seed puts the same sizes near the window's edges: how much of a long
#: request falls inside the window then varies little from seed to seed
#: (a reorder over the whole window spread the output rate by 12-16%).
BLOCK = 4


def _local_permutation(n: int, rng: np.random.Generator) -> np.ndarray:
    idx = np.arange(n)
    for b in range(0, n, BLOCK):
        idx[b:b + BLOCK] = b + rng.permutation(min(BLOCK, n - b))
    return idx


def schedule(mix: dict, arrival: dict, *, seed: int, segments_s,
             vocab: int) -> list[Request]:
    """Requests due over consecutive segments of ``segments_s`` seconds,
    ``ceil(rate * length)`` in each, at the cell's mean rate."""
    rate = float(arrival["rate_per_s"])
    rng = np.random.default_rng(seed)
    out: list[Request] = []
    t0 = 0.0
    for k, span in enumerate(segments_s):
        n = max(1, math.ceil(rate * span))
        fixed = np.random.default_rng([SIZE_SEED, k])
        sizes = np.stack([lengths(mix["prompt"], n, fixed),
                          lengths(mix["output"], n, fixed)], 1)
        gap = gaps(arrival, n, span, fixed)
        sizes = sizes[_local_permutation(n, rng)]
        gap = gap[_local_permutation(n, rng)]
        due = t0 + np.concatenate([[0.0], np.cumsum(gap)[:-1]])
        out += [Request(float(due[i]),
                        tuple(int(t) for t in rng.integers(0, vocab,
                                                           sizes[i, 0])),
                        int(sizes[i, 1]))
                for i in range(n)]
        t0 += span
    return out
