"""A kernel's share of the chip's bf16 peak: the FLOPs a step REQUIRES of
it (``benchlib/routed.py``, from shapes and the program's own counts) over
the device time a step spends in the operations named ``kernels`` (prefixes
of HLO instruction names, as the trace has them) over the peak.  The
operations come from the driver's full list where it kept one
(``counts["device_ops_all"]``: ``run.py`` hands on only the ten largest),
else from those ten.  Nothing to read -> None."""

from benchlib import peaks, routed


def read(run, kernels, flops, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace:
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    if flops == "causal_attention":
        need = routed.causal_attention_flops(
            tr["rows"] * cell.chips, tr["seq"], cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["num_hidden_layers"],
        )
    elif flops == "experts":
        if not counts.get("local_assignments_per_step"):
            return None
        need = routed.expert_flops(
            counts["local_assignments_per_step"], cell.counts["expert_params"]
        )
    else:
        raise ValueError(f"unknown flops: {flops!r}")
    peak = peaks.peaks(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * need / seconds / (peak * cell.chips)
