"""The plain (not banded) flash kernels' share of the chip's bf16 peak in
a family that mixes full and window attention layers: the FLOPs the FULL
layers of a step REQUIRE (``benchlib/routed.causal_attention_flops``: the
causal triangle at the heads' width, forward + backward, nothing
recomputed, ``n_full_layers`` of them) over the device time a step spends
in the operations named ``kernels`` (prefixes of HLO instruction names, as
the trace has them: ``flash_fwd``, ``flash_bwd_``, which no banded kernel's
name starts with) over the peak.  The operations come from the driver's
full list where it kept one (``counts["device_ops_all"]``), else from the
ten ``run.py`` hands on.  Nothing to read (no trace, no such kernel in it,
a family that counts no full layers) -> None."""

from benchlib import peaks, routed


def read(run, kernels, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace or not cell.counts.get("n_full_layers"):
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    need = routed.causal_attention_flops(
        tr["rows"] * cell.chips, tr["seq"], cfg["num_attention_heads"],
        cfg["head_dim"], cfg["head_dim"], cell.counts["n_full_layers"],
    )
    peak = peaks.peaks(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * need / seconds / (peak * cell.chips)
