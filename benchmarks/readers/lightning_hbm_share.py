"""The lightning kernels' share of the chip's HBM roofline: the bytes the
lightning layers of a step MUST move (``benchlib/sparse.lightning_bytes``:
q, k, v, o forward, q, k, v, do, dq, dk, dv backward) over the device time
a step spends in the operations named ``kernels`` over the peak bandwidth.
The bytes bound binds (a chunk of 256 does some 200 FLOPs a byte less than
the chip's 240), as for ``ssm_scan_hbm_pct``.  Nothing to read -> None."""

from benchlib import peaks, routed, sparse


def read(run, kernels, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace or not cell.counts.get("n_lightning_layers"):
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    need = sparse.lightning_bytes(
        tr["rows"] * cell.chips, tr["seq"], cfg["lightning_nh"],
        cfg["lightning_head_dim"], cell.counts["n_lightning_layers"],
        cell.itemsize,
    )
    bw = peaks.peaks(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / seconds / (bw * cell.chips)
