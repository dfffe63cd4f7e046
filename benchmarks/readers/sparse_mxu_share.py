"""The block-sparse kernels' share of the chip's bf16 peak: the FLOPs the
sparse layers of a step REQUIRE (``benchlib/sparse.py``: the pairs each
token chose, forward + backward, nothing recomputed) over the device time
a step spends in the operations named ``kernels`` (prefixes of HLO
instruction names, as the trace has them) over the peak.  A kernel that
computes pairs no token chose (a tile's union is wider than each of its
tokens' choice) gains nothing by them, so the share cannot read over 100.
Nothing to read (no trace, no such kernel in it, a family with no sparse
layer) -> None."""

from benchlib import peaks, routed, sparse


def read(run, kernels, program="step_fn"):
    trace, cell, counts = run["trace"], run["cell"], run["counts"]
    if not trace or not cell.counts.get("n_sparse_layers"):
        return None
    ops = counts.get("device_ops_all") or trace["device_ops"]
    seconds = routed.kernel_seconds_per_step(trace, ops, program, kernels)
    if seconds is None:
        return None
    cfg, tr = cell.config, cell.config["training"]
    need = sparse.sparse_attention_flops(
        tr["rows"] * cell.chips, tr["seq"], cfg["sparse_block_size"],
        cfg["sparse_topk"], cfg["sparse_dense_len"],
        cfg["num_attention_heads"], cfg["head_dim"], cfg["head_dim"],
        cell.counts["n_sparse_layers"],
    )
    peak = peaks.peaks(run["peaks_kind"])["bf16_flops_per_s"]
    return 100.0 * need / seconds / (peak * cell.chips)
