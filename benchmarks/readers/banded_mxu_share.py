"""The banded flash kernels' share of the chip's bf16 peak: the FLOPs the
window layers of a step REQUIRE (``benchlib/banded.py``: pairs inside the
band only, forward + backward, nothing recomputed) over the device time a
step spends in the operations named ``kernels`` (prefixes of HLO
instruction names, as the trace has them) over the peak.  A kernel that
computes masked pairs gains nothing by it, so the share cannot read over
100.  The operations come from the driver's full list where it kept one
(``counts["device_ops_all"]``), else from the ten ``run.py`` hands on.
Nothing to read (no trace, no such kernel in it, a family with no window
layers) -> None."""

from benchlib import banded, peaks, routed


def read(run, kernels, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace or not cell.counts.get("n_window_layers"):
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    need = banded.window_attention_flops(
        tr["rows"] * cell.chips, tr["seq"], cfg["sliding_window"],
        cfg["num_attention_heads"], cfg["head_dim"], cfg["head_dim"],
        cell.counts["n_window_layers"],
    )
    peak = peaks.peaks(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * need / seconds / (peak * cell.chips)
