"""One of the program's own counters, or one field of one of its
histograms, as it stands when the run's metrics are read: after the window,
which for a compile counter is also its value before the window (a compile
inside the window makes the run incorrect).  A name the program does not
count has nothing to read."""


def read(run, counter=None, histogram=None, field="sum"):
    from torchdistx_tpu import telemetry

    if counter is not None:
        return telemetry.counters().get(counter) or None
    return (telemetry.histograms().get(histogram) or {}).get(field)
