#!/usr/bin/env python3
"""Find an open-loop cell's knee; not run by the driver.

    python3 benchmarks/sweep.py --workload <cell> --seconds <s> --rates 0.7,0.9,1.1,1.3,1.5

ONE process builds the engine once and offers the cell's traffic at each
rate in turn, with the cell's own pre-roll, a window of ``--seconds`` and
the cell's drain; between rates it drains to empty.  The knee is the highest
rate at which every request finishes within the drain and no more requests
are open at the window's end than at its middle.  The cell then runs at
about four fifths of it; the number goes into the cell's file by hand and
the table into PERF.md.
"""

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    device = bench.gate(1, args.rehearse)
    bench.open_compile_cache()
    cell = bench.load_cell(args.workload, args.seed, args.rehearse)
    driver = importlib.import_module(f"drivers.{cell.workload['driver']}")
    state = driver.build(cell, bench.make_params(cell))
    tracer = bench.Tracer(False, 0, 0)
    run = {"cell": cell, "trace": None, "device": device}
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        res = driver.run(cell, state, args.seconds, tracer, rate=rate)
        state["eng"].drain()
        run.update(counts=res["counts"], records=res["records"])
        row = {
            "rate_rps": rate, "requests": res["attempted"],
            "failed": res["failed"], "open_mid": res["backlog"].get("mid"),
            "open_end": res["backlog"].get("end"),
        }
        for name in ("ttft_p90_ms", "tpot_p90_ms", "serve_tok_s",
                     "queue_wait_mean_ms", "decode_step_ms"):
            m = bench.read_metrics([name], run).get(name)
            row[name] = None if args.rehearse or m is None else m["value"]
        rows.append(row)
        bench.say(res["log"][1])
        bench.say(json.dumps(row))
    os.makedirs("chiprun_out", exist_ok=True)
    with open(f"chiprun_out/sweep_{args.workload}.json", "w") as f:
        json.dump({"device": device, "seconds": args.seconds, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
