"""A percentile of one field over the requests counted in the window, a
failed or unfinished request ranking above every finished one."""

from benchlib import stats


def read(run, field, p, scale=1.0):
    recs = [r for r in run["records"] if r["phase"] == "window"]
    v = stats.percentile(
        [r[field] for r in recs], [r["failed"] for r in recs], p
    )
    return None if v is None else v * scale
