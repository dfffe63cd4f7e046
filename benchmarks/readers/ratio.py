"""``num / den * scale`` over the run's counts (window deltas of the
program's counters, the driver's totals, the parts of set-up).  ``den`` may
be left out (1).  Nothing to read -> None, and the metric is left out."""


def read(run, num, den=None, scale=1.0):
    counts = run["counts"]
    n = counts.get(num)
    d = 1.0 if den is None else counts.get(den)
    if n is None or not d:
        return None
    return n / d * scale
