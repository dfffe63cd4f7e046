#!/usr/bin/env python3
"""The traced run of a cell (``run.py --trace 1``) with the train step's
device time read by named scope.

    python3 benchmarks/run_scopes.py --workload gpt2-xl.pretrain --seed <n> --seconds <s>

``run.py``, ``benchlib/trace.py`` and the cell files are not this PR's to
edit (only a ``benchmark`` PR may change what the accepted benchmark has),
so until one makes the three edits named in ``PERF.md`` section 7 this file
makes them in memory and then runs ``run.py`` as it is: the trace's
reduction gains the key ``scopes`` (``benchlib/scopes.py``, joined with the
program's ``telemetry.perf.program_scopes()``), the cell's per-layer list
gains the nine metrics that read it (``metrics/step_*_ms.json``,
``metrics/train_step_compile*.json``), and the log gains the table scope x
phase, the flash kernels' calls per step, the ten largest unscoped
operations and what recording the scope map cost.  The last line keeps
``run.py``'s keys.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as bench  # noqa: E402
from benchlib import scopes, trace  # noqa: E402

# A compiled program's name in the trace -> its label in the perf plane.
PROGRAMS = {"step_fn": "train_step"}
METRICS = [
    "step_flash_ms", "step_attn_ms", "step_mlp_ms", "step_head_ms",
    "step_optimizer_ms", "step_guard_ms", "step_unscoped_ms",
    "train_step_compile_s", "train_step_compiles",
]


def main(argv=None) -> int:
    reduce_trace, load_cell = trace.reduce, bench.load_cell

    def reduce_with_scopes(path, top=10):
        from torchdistx_tpu import telemetry
        from torchdistx_tpu.telemetry import perf

        out = reduce_trace(path, top)
        # A program from before the scope maps has none: nothing to add.
        recorded = getattr(perf, "program_scopes", dict)()
        maps = {
            name: recorded[label] for name, label in PROGRAMS.items()
            if label in recorded
        }
        if out is not None and maps:
            out["scopes"] = scopes.reduce(path, maps)
            for name, p in out["scopes"].items():
                bench.say(scopes.table(name, p))
        for rec in telemetry.snapshot()["spans"]:
            if rec.get("name") == "perf.scope_map":
                bench.say(
                    f"scope map of {rec['attrs']['program']}: recorded in "
                    f"{rec['dur_s']:.2f} s of set-up"
                )
        return out

    def load_with_metrics(name, seed, rehearse):
        cell = load_cell(name, seed, rehearse)
        cell.workload["per_layer"] = cell.workload["per_layer"] + METRICS
        return cell

    trace.reduce, bench.load_cell = reduce_with_scopes, load_with_metrics
    args = sys.argv[1:] if argv is None else list(argv)
    return bench.main([*args, "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
