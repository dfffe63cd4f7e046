"""A kernel's share of the chip's HBM roofline: the bytes a step REQUIRES
of it (``benchlib/ssm.py``, from shapes) over the device time a step spends
in the operations named ``kernels`` (prefixes of HLO instruction names, as
the trace has them) over the peak bandwidth; with ``bytes`` null, that
device time itself in milliseconds.  The operations come from the driver's
full list where it kept one (``counts["device_ops_all"]``), else from the
ten ``run.py`` hands on.  Nothing to read -> None."""

from benchlib import peaks, routed, ssm


def read(run, kernels, bytes=None, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace:
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    if bytes is None:
        return 1e3 * seconds
    if bytes != "selective_scan":
        raise ValueError(f"unknown bytes: {bytes!r}")
    tr = cell.config["training"]
    need = ssm.selective_scan_bytes(
        tr["rows"] * cell.chips, tr["seq"], cell.counts["d_inner"],
        cell.config["mamba_d_state"], cell.counts["n_mamba_layers"],
        cell.itemsize,
    )
    bw = peaks.peaks(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / seconds / (bw * cell.chips)
