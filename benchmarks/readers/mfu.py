"""Model FLOP/s utilization: the FLOPs the forward and backward passes
REQUIRE per token (from shapes, nothing recomputed) times tokens per second
of the whole window, over the chips' bf16 peak."""

from benchlib import peaks, shapes


def read(run):
    cell, counts = run["cell"], run["counts"]
    if not counts.get("tokens") or not counts.get("window_s"):
        return None
    c = cell.counts
    flops = shapes.train_flops_per_token(
        c["matmul_params"], c["n_layers"], c["d_attn"],
        cell.config["training"]["seq"],
    )
    peak = peaks.peaks(run["peaks_kind"])["bf16_flops_per_s"]
    rate = counts["tokens"] / counts["window_s"]
    return 100.0 * flops * rate / (peak * cell.chips)
