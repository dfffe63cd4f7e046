"""Order statistics over requests, with failures ranked last."""

import math
from typing import Optional, Sequence


def percentile(
    values: Sequence[float], failed: Sequence[bool], p: float
) -> Optional[float]:
    """Nearest-rank ``p``-th percentile where a failed or unfinished
    request ranks above every finished one (its value is then the time it
    had been waiting when the run gave up on it: a lower bound)."""
    ranked = sorted(zip(failed, values))
    if not ranked:
        return None
    return float(ranked[max(0, math.ceil(p / 100 * len(ranked)) - 1)][1])

