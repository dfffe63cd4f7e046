"""What the serving drivers share: the engine from the configuration's
serving block, a warm-up of exactly the programs the cell's lengths can
reach, per-request records on the BENCHMARK's clock, and the reference
check of finished streams.

The engine is single-threaded and the benchmark drives it: a consumer sees
a token when ``step()`` returns, so that is when a token has arrived.
"""

import gc
import time

import numpy as np

from benchlib import traffic


def build(cell, params):
    from torchdistx_tpu.serving import Engine

    sv = cell.config["serving"]
    eng = Engine(
        params, model=cell.model, cfg=cell.cfg, num_slots=sv["num_slots"],
        block_size=sv["block_size"], num_blocks=sv["num_blocks"],
        max_model_len=sv["max_model_len"], prefill_chunk=sv["prefill_chunk"],
        decode_chunk=sv["decode_chunk"], handle_preemption=False,
    )
    # One prompt per prefill program the cell can reach (the last-chunk
    # buckets, the full non-final chunk) and the decode chunk; a repeated
    # prompt is a full prefix hit, which warms the copy-on-write copy.
    rng = np.random.default_rng(12345)
    t = time.perf_counter()
    handles = []
    for n in cell.workload["warm_prompts"]:
        if n < 0:  # repeat the previous prompt
            prompt = handles[-1][0]
        else:
            prompt = traffic.tokens(rng, n, cell.config["vocab_size"])
        handles.append(
            (prompt, eng.submit(prompt, max_new_tokens=2))
        )
        while not handles[-1][1].done:
            eng.step()
    st = {"eng": eng, "slots": sv["num_slots"]}
    st["warm_log"] = (
        f"engine: {sv['num_slots']} slots, {sv['num_blocks']} pages of "
        f"{sv['block_size']}, max_model_len {sv['max_model_len']}; warmed "
        f"{cell.workload['warm_prompts']} in {time.perf_counter() - t:.2f}s"
    )
    return st


def counters() -> dict:
    from torchdistx_tpu import telemetry

    return telemetry.counters()


class Tracker:
    """Per-request records.  Times are ``time.perf_counter()`` readings
    taken by the benchmark; ``admit_t`` is the engine's own mark of the
    request's admission, on the same clock."""

    def __init__(self):
        self.records, self.open, self.delivered = [], [], []

    def add(self, handle, prompt, due, phase, **extra):
        rec = {
            "prompt": prompt, "n_prompt": len(prompt), "due": due,
            "submit_t": time.perf_counter(), "phase": phase, "handle": handle,
            "first_t": None, "done_t": None, "n_out": 0, "error": None,
            **extra,
        }
        self.records.append(rec)
        self.open.append(rec)
        return rec

    def poll(self, now: float) -> list:
        """Read every open handle; returns the records that just ended."""
        ended = []
        for rec in self.open:
            h = rec["handle"]
            n = len(h._tokens)
            if n > rec["n_out"]:
                self.delivered.append((now, n - rec["n_out"]))
            rec["n_out"] = n
            if rec["first_t"] is None and rec["n_out"]:
                rec["first_t"] = now
            if h.done:
                rec["done_t"], rec["error"] = now, h.error
                ended.append(rec)
        if ended:
            self.open = [r for r in self.open if r["done_t"] is None]
        return ended

    def delivered_between(self, t0: float, t1: float) -> int:
        """Output tokens that reached their callers in ``[t0, t1]``."""
        return sum(n for t, n in self.delivered if t0 <= t <= t1)

    def live_positions(self) -> int:
        """Cached positions a decode step reads now: prompt + generated of
        every stream that has its first token and is not done."""
        return sum(
            r["n_prompt"] + r["n_out"] for r in self.open
            if r["first_t"] is not None
        )


def finish(rec: dict, giveup_t: float) -> dict:
    """Freeze one record into what the readers see."""
    h = rec.pop("handle")
    req = h._req
    ok = rec["done_t"] is not None and rec["error"] is None
    waited = giveup_t - rec["due"]
    rec["failed"] = not ok
    rec["tokens"] = list(h._tokens)
    rec["n_cached"] = int(req.n_cached)
    rec["queue_wait_s"] = (
        req.admit_t - rec["due"] if req.admit_t is not None else waited
    )
    rec["ttft_s"] = (
        rec["first_t"] - rec["due"] if rec["first_t"] is not None else waited
    )
    rec["tpot_s"] = (
        (rec["done_t"] - rec["first_t"]) / (rec["n_out"] - 1)
        if ok and rec["n_out"] > 1 else waited
    )
    return rec


def snapshot(eng) -> dict:
    s = eng.stats()
    return {
        "decode_s": s["decode_s"],
        "decode_steps": eng._decode_no * eng.decode_chunk,
        "decode_tokens": s["decode_tokens"],
        "prefix_hit_tokens": s.get("prefix_hit_tokens", 0),
        "prefix_evictions": s.get("prefix_evictions", 0),
        "ticks": s["ticks"],
    }


def delta(s0: dict, s1: dict) -> dict:
    return {k: s1[k] - s0[k] for k in s0}


def check(cell, st, result):
    """After the window, the engine gone and the weights made anew from the
    seed: for a seeded sample of finished streams, every generated token's
    reference logit within ``tol`` of the reference's best there."""
    import jax

    st.pop("eng").close()
    gc.collect()
    held = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    want = cell.workload["reference_streams"]
    done = [
        r for r in result["records"]
        if r["phase"] == "window" and not r["failed"]
    ]
    rng = np.random.default_rng(cell.seed + 1)
    hits = [r for r in done if r["n_cached"] > 0]
    cold = [r for r in done if r["n_cached"] == 0]
    picked = []
    for group, k in ((hits, want // 2), (cold, want)):
        if group:
            idx = rng.permutation(len(group))[: max(0, min(k, want - len(picked)))]
            picked += [group[i] for i in idx]
    if not picked:
        return False, "no finished stream to check"
    sv, tol = cell.config["serving"], cell.config["tol"]["logit_gap"]
    n_rows = -(-max(r["max_new"] for r in result["records"]) // 64) * 64
    params = cell.make_params()
    worst, rows = 0.0, []
    for r in picked:
        gaps = cell.check.stream_gaps(
            cell.ref, params, cell.config, r["prompt"], r["tokens"],
            sv["max_model_len"], n_rows,
        )
        worst = max(worst, float(gaps.max()))
        rows.append(
            f"{r['n_prompt']}+{len(r['tokens'])} tokens "
            f"({r['n_cached']} cached): worst {gaps.max():.4f} at generated "
            f"token {int(gaps.argmax())}"
        )
    del params
    return worst <= tol, (
        f"(engine closed, {held / 1e9:.2f} GB still held on the device) "
        f"{len(picked)} streams, {sum(len(r['tokens']) for r in picked)} "
        f"generated tokens; worst gap to the reference's best logit "
        f"{worst:.4f}, tolerance {tol} [" + "; ".join(rows) + "]"
    )


def traced_counts(cell, result, tracer, telemetry):
    """Counts over the traced stretch only: the mean of the live cached
    positions, and the prompt tokens the prefill programs computed there
    (the program's ``serve.prefill`` spans that lie wholly inside)."""
    live = [
        p for t, p, _ in result["samples"] if tracer.t0 <= t <= tracer.t1
    ]
    spans = [
        s for s in telemetry.snapshot()["spans"]
        if s.get("name") == "serve.prefill"
        and s["ts"] >= tracer.wall0 and s["ts"] + s["dur_s"] <= tracer.wall1
    ]
    return {
        "live_positions_traced": float(np.mean(live)) if live else None,
        "prefill_tokens_traced": sum(s["attrs"]["n"] for s in spans),
        "decode_chunk": cell.config["serving"]["decode_chunk"],
    }
