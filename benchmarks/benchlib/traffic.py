"""One general traffic generator, driven by the parameters in a cell's file.

Every seed gets the SAME multiset of sizes and arrival gaps — the quantile
grid of the distribution the cell names — in another order, so the seed
changes which request meets which and never how much work a run holds.
Token contents are drawn from the seed.
"""

from statistics import NormalDist

import numpy as np


def grid(spec: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles of ``spec``'s distribution,
    ascending, clipped to ``lo``/``hi``; whole numbers unless
    ``"int": false``."""
    q = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "uniform":
        v = spec["lo"] + (spec["hi"] - spec["lo"]) * q
    elif dist == "exponential":
        v = -np.log1p(-q) * spec.get("mean", 1.0)
    elif dist == "gamma":
        # cv = 1/sqrt(shape); quantiles of a large fixed sample stand in
        # for the inverse CDF (no closed form).
        shape = 1.0 / spec["cv"] ** 2
        sample = np.random.default_rng(0).gamma(
            shape, spec.get("mean", 1.0) / shape, 200_000
        )
        v = np.quantile(sample, q)
    elif dist == "fixed":
        v = np.full(n, float(spec["value"]))
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    if "lo" in spec or "hi" in spec:
        v = np.clip(v, spec.get("lo", -np.inf), spec.get("hi", np.inf))
    return np.rint(v).astype(np.int64) if spec.get("int", True) else v


def arrivals(rng, rate: float, seconds: float, gaps: dict) -> np.ndarray:
    """Arrival offsets in ``(0, seconds)``: ``round(rate * seconds)`` gaps
    on ``gaps``' quantile grid, scaled to fill the span exactly, in the
    seed's order.  Each arrival sits in the middle of its gap."""
    n = max(1, int(round(rate * seconds)))
    g = grid({**gaps, "int": False}, n)
    g = rng.permutation(g * (seconds / g.sum()))
    return np.cumsum(g) - g / 2


def zipf_block(n_items: int, s: float, block: int) -> np.ndarray:
    """Item indices of one block of ``block`` draws whose counts follow
    Zipf(``s``) over ranks 1..n_items by largest remainder; unshuffled."""
    p = 1.0 / np.arange(1, n_items + 1) ** s
    want = p / p.sum() * block
    counts = np.floor(want).astype(int)
    order = np.argsort(-(want - counts), kind="stable")
    counts[order[: block - counts.sum()]] += 1
    return np.repeat(np.arange(n_items), counts)


def tokens(rng, n: int, vocab: int) -> np.ndarray:
    return rng.integers(0, vocab, size=int(n), dtype=np.int64).astype(np.int32)
