"""Device seconds of the named compiled programs in the traced stretch,
over a count taken over the same stretch."""


def read(run, programs, per, scale=1.0):
    progs = (run["trace"] or {}).get("programs", {})
    n = run["counts"].get(per)
    if not n or not any(p in progs for p in programs):
        return None
    return sum(progs[p]["device_s"] for p in programs if p in progs) / n * scale
