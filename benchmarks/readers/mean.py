"""The mean of one field over the requests counted in the window."""


def read(run, field, scale=1.0):
    vals = [r[field] for r in run["records"] if r["phase"] == "window"]
    return sum(vals) / len(vals) * scale if vals else None
