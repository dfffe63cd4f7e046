"""Open loop: requests arrive on the wall clock at a rate fixed in the
cell's file, whether or not earlier ones have finished.  A request is timed
from when it was DUE, not from when the loop got round to submitting it, and
the generator's lateness is reported.  ``preroll_s`` of the same traffic
runs before the window; the requests counted are those due inside the
window; after it the loop drains for at most ``drain_s`` and what is
unfinished then has failed.
"""

import time

import numpy as np

from benchlib import traffic
from drivers import serving

build = serving.build
check = serving.check
traced_counts = serving.traced_counts


def plan(cell, seconds: float, rate: float):
    """(offset, phase, prompt, max_new) for pre-roll and window.  Sizes and
    gaps are the quantile grids of the cell's distributions in the order
    ``schedule_seed`` (in the cell's file) gives them: the SAME schedule in
    every run, so that a tail over some forty requests repeats; ``--seed``
    draws the token ids (and the weights)."""
    tr = cell.workload["traffic"]
    order = np.random.default_rng(tr["schedule_seed"])
    rng = np.random.default_rng(cell.seed)
    out, base = [], 0.0
    for phase, span in (("preroll", tr["preroll_s"]), ("window", seconds)):
        offs = traffic.arrivals(order, rate, span, tr["gaps"])
        p = order.permutation(traffic.grid(tr["prompt"], len(offs)))
        o = order.permutation(traffic.grid(tr["output"], len(offs)))
        out += [
            (base + off, phase,
             traffic.tokens(rng, n, cell.config["vocab_size"]), int(m))
            for off, n, m in zip(offs, p, o)
        ]
        base += span
    return out


def run(cell, st, seconds, tracer, rate=None):
    eng, tr = st["eng"], cell.workload["traffic"]
    rate = tr["rate_rps"] if rate is None else rate
    todo = plan(cell, seconds, rate)
    track = serving.Tracker()
    counters0 = serving.counters()
    run_start = time.perf_counter()
    t_win = run_start + tr["preroll_s"]
    t_end = t_win + seconds
    t_stop = t_end + tr["drain_s"]
    i, late, samples = 0, [], []
    s0 = s1 = None
    backlog = {}
    while True:
        now = time.perf_counter()
        if s0 is None and now >= t_win:
            s0 = serving.snapshot(eng)
        if s1 is None and now >= t_end:
            s1 = serving.snapshot(eng)
            backlog["end"] = len(track.open)
        if "mid" not in backlog and now >= t_win + seconds / 2:
            backlog["mid"] = len(track.open)
        tracer.tick(now - t_win)
        while i < len(todo) and run_start + todo[i][0] <= now:
            off, phase, prompt, max_new = todo[i]
            with tracer.span("bench.submit"):
                h = eng.submit(prompt, max_new_tokens=max_new, key=i)
            track.add(h, prompt, run_start + off, phase, max_new=max_new)
            late.append(time.perf_counter() - (run_start + off))
            i += 1
        if now >= t_end and (
            now >= t_stop
            or not any(r["phase"] == "window" for r in track.open)
        ):
            break
        if not track.open:
            nxt = run_start + todo[i][0] if i < len(todo) else t_end
            with tracer.span("bench.idle_sleep"):
                time.sleep(max(0.0, min(nxt - time.perf_counter(), 0.05)))
            continue
        with tracer.span("bench.step"):
            eng.step()
        with tracer.span("bench.poll"):
            now = time.perf_counter()
            track.poll(now)
            samples.append(
                (now, track.live_positions(), eng.stats()["running"])
            )
    giveup = time.perf_counter()
    records = [serving.finish(r, giveup) for r in track.records]
    window = [r for r in records if r["phase"] == "window"]
    done = [r for r in window if not r["failed"]]
    counts = serving.delta(s0, s1)
    counts.update(
        window_s=seconds,
        output_tokens=track.delivered_between(t_win, t_end),
        prompt_tokens=sum(r["n_prompt"] for r in window),
        occupancy_sum=sum(
            run / st["slots"] for t, _, run in samples if t_win <= t <= t_end
        ),
        occupancy_n=sum(1 for t, _, _ in samples if t_win <= t <= t_end),
    )
    late_ms = np.array(late) * 1e3
    return {
        "run_start": run_start, "window_start": t_win, "lead_in": "pre-roll", "window_s": seconds,
        "attempted": len(window), "failed": len(window) - len(done),
        "counts": counts, "records": records, "samples": samples,
        "counters0": counters0,
        "backlog": backlog,
        "log": [
            st["warm_log"],
            f"open loop at {rate} requests/s: window {seconds}s (+"
            f"{tr['preroll_s']}s pre-roll), {len(window)} requests due in "
            f"the window, {len(window) - len(done)} failed or unfinished "
            f"after a drain of {giveup - t_end:.1f}s; requests open at "
            f"mid-window {backlog.get('mid')}, at its end {backlog.get('end')}"
            f"; generator lateness ms p50 {np.median(late_ms):.1f} max "
            f"{late_ms.max():.1f}",
            f"engine stats at the end: {eng.stats()}",
        ],
    }
