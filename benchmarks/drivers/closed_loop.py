"""Closed loop: ``clients`` callers, each sending its next request the
moment its previous one completes, no think time.  A corpus of documents is
made from the seed; each request is a document drawn by Zipf plus a fresh
question.  ``preroll_s`` of the same traffic runs before the window so the
prefix cache is in its steady state; the requests counted are those that
COMPLETE inside the window, and the loop stops at its end.

Every run meets the same work in the same order: document lengths by
popularity rank, and the sequence of documents, question and answer lengths
(blocks that each hold the distribution's whole quantile grid) come from
``schedule_seed`` in the cell's file, so the sharing, the evictions and the
lengths repeat from run to run; ``--seed`` draws the token ids (and the
weights).
"""

import time

import numpy as np

from benchlib import traffic
from drivers import serving

build = serving.build
check = serving.check
traced_counts = serving.traced_counts


def requests(cell):
    """An endless stream of (prompt, max_new) from the seed."""
    tr, vocab = cell.workload["traffic"], cell.config["vocab_size"]
    rng = np.random.default_rng(cell.seed)
    order = np.random.default_rng(tr["schedule_seed"])
    lengths = order.permutation(traffic.grid(tr["document"], tr["documents"]))
    corpus = [traffic.tokens(rng, n, vocab) for n in lengths]
    block = tr["block"]
    while True:
        docs = order.permutation(
            traffic.zipf_block(tr["documents"], tr["zipf_s"], block)
        )
        qs = order.permutation(traffic.grid(tr["question"], block))
        outs = order.permutation(traffic.grid(tr["answer"], block))
        for d, q, o in zip(docs, qs, outs):
            yield (
                np.concatenate([corpus[d], traffic.tokens(rng, q, vocab)]),
                int(o),
            )


def run(cell, st, seconds, tracer):
    eng, tr = st["eng"], cell.workload["traffic"]
    stream = requests(cell)
    first = [next(stream) for _ in range(tr["clients"])]
    track = serving.Tracker()
    counters0 = serving.counters()
    run_start = time.perf_counter()
    t_win = run_start + tr["preroll_s"]
    t_end = t_win + seconds
    samples, n_sub = [], 0
    s0 = None

    def submit(prompt, max_new):
        nonlocal n_sub
        with tracer.span("bench.submit"):
            h = eng.submit(prompt, max_new_tokens=max_new, key=n_sub)
        now = time.perf_counter()
        track.add(
            h, prompt, now, "window" if now >= t_win else "preroll",
            max_new=max_new,
        )
        n_sub += 1

    for prompt, max_new in first:
        submit(prompt, max_new)
    while True:
        now = time.perf_counter()
        if s0 is None and now >= t_win:
            s0 = serving.snapshot(eng)
        if now >= t_end:
            break
        tracer.tick(now - t_win)
        with tracer.span("bench.step"):
            eng.step()
        with tracer.span("bench.poll"):
            now = time.perf_counter()
            ended = track.poll(now)
            samples.append(
                (now, track.live_positions(), eng.stats()["running"])
            )
        for _ in ended:  # each caller sends its next request at once
            submit(*next(stream))
    s1 = serving.snapshot(eng)
    giveup = time.perf_counter()
    window_s = giveup - t_win
    records = [serving.finish(r, giveup) for r in track.records]
    for r in records:  # counted where it completed, not where it began
        r["phase"] = (
            "window" if r["done_t"] is not None and r["done_t"] >= t_win
            else "open" if r["done_t"] is None else "preroll"
        )
    done = [r for r in records if r["phase"] == "window" and not r["failed"]]
    failed = [r for r in records if r["phase"] == "window" and r["failed"]]
    counts = serving.delta(s0, s1)
    counts.update(
        window_s=window_s,
        output_tokens=track.delivered_between(t_win, giveup),
        prompt_tokens=sum(
            r["n_prompt"] for r in records if r["submit_t"] >= t_win
        ),
        occupancy_sum=sum(
            run / st["slots"] for t, _, run in samples if t >= t_win
        ),
        occupancy_n=sum(1 for t, _, _ in samples if t >= t_win),
    )
    return {
        "run_start": run_start, "window_start": t_win, "lead_in": "pre-roll", "window_s": window_s,
        "attempted": len(done) + len(failed), "failed": len(failed),
        "counts": counts, "records": records, "samples": samples,
        "counters0": counters0,
        "log": [
            st["warm_log"],
            f"closed loop of {tr['clients']} clients: window {window_s:.2f}s "
            f"(+{tr['preroll_s']}s pre-roll), {len(done)} requests completed "
            f"in it, {len(failed)} failed, {n_sub} submitted in all",
            f"engine stats at the end: {eng.stats()}",
        ],
    }
