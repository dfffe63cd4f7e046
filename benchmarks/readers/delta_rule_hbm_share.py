"""The delta rule kernels' share of the chip's HBM roofline: the bytes the
linear layers of a step MUST move (``benchlib/delta.delta_rule_bytes``: q,
k, v, g, beta, o forward, those, do and their gradients backward) over the
device time a step spends in the operations named ``kernels`` over the
peak bandwidth.  It reads low by construction: the chunk form does some
``6 C + 2 (d_k + d_v)`` products a position and head on tiles of ``C``
(the inverse, the corrected values, the state's products), bound by the
vector unit's and the MXU's latency on small tiles, not by the bytes, as
``ssm_scan_hbm_pct`` reads the selective scan.  Nothing to read -> None."""

from benchlib import delta, peaks, routed


def read(run, kernels, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace or not cell.counts.get("n_linear_layers"):
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    need = delta.delta_rule_bytes(
        tr["rows"] * cell.chips, tr["seq"], cfg["linear_num_key_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
        cell.counts["n_linear_layers"], cell.itemsize,
    )
    bw = peaks.peaks(run["peaks_kind"])["hbm_bytes_per_s"]
    return 100.0 * need / seconds / (bw * cell.chips)
