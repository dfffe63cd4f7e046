"""The yardstick of the SmallThinker cell: counts from shapes against
hand-worked values, the file against the catalog, and the reader of the
FULL layers' flash kernels on a made-up reduction and on the recorded
trace (``fixtures/scoped_step.xplane.pb``)."""

import importlib
import json
import os
import types

import pytest

from benchlib import banded, routed, shapes, trace
from conftest import BENCH, ROOT

CONFIG = "smallthinker-21ba3b-instruct"
CELL = f"{CONFIG}.pretrain-16k"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _counts(config):
    return importlib.import_module("families.smallthinker").counts(config)


def test_counts_by_hand():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    n = _counts(c)
    # W_q and W_o 2560 x 3584 each; W_k and W_v 2560 x 512
    attn = 2 * 9_175_040 + 2 * 1_310_720
    assert n["attn_params"] == attn == 20_971_520
    expert = 3 * 2560 * 768
    assert n["expert_params"] == expert == 5_898_240
    # router 2560 x 64; 6 x 16/64 routed in expectation; nothing shared
    per_layer = attn + 163_840 + 6 * expert // 4
    assert per_layer == 29_982_720
    layers = c["num_hidden_layers"]
    assert n["matmul_params"] == layers * per_layer + 37984 * 2560
    assert (n["n_layers"], n["n_moe_layers"]) == (layers, layers)
    assert (n["n_window_layers"], n["n_full_layers"]) == (3 * layers // 4, layers // 4)
    window, triangle = 58_722_304, 134_225_920
    assert banded.window_pairs(16384, 4096) == window
    required = n["n_window_layers"] * window + n["n_full_layers"] * triangle
    assert n["d_attn"] == 3584 * required // (layers * triangle)
    # what train_mfu_pct charges attention is no more than what is required
    charged = 6 * n["d_attn"] * 16385 * layers * 16384
    need = (
        banded.window_attention_flops(1, 16384, 4096, 28, 128, 128, n["n_window_layers"])
        + routed.causal_attention_flops(1, 16384, 28, 128, 128, n["n_full_layers"])
    )
    assert 0.999 * need < charged <= need
    if layers == 4:  # the memory rule's outcome (B)
        assert shapes.train_flops_per_token(
            n["matmul_params"], 4, n["d_attn"], 16384
        ) * 16384 == pytest.approx(34.7e12, rel=0.01)


def test_the_file_holds_the_published_config_and_the_share():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        row = next(
            r for r in map(json.loads, f)
            if r["name"] == "SmallThinker-21BA3B-Instruct"
        )
    assert row["source_url"] == c["source"]
    differ = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differ == set(c["reduced"]) == {
        "num_hidden_layers", "moe_num_primary_experts", "vocab_size",
        "rope_layout", "sliding_window_layout",
    }
    (entry,) = [
        x for x in _json(ROOT, "BENCHMARK.json")["configs"] if x["name"] == CONFIG
    ]
    assert set(entry["reduced"]) == differ and entry["source"] == c["source"]
    assert c["published"]["moe_num_primary_experts"] == 64
    assert c["moe_num_primary_experts_total"] == 64 == 4 * c["moe_num_primary_experts"]
    assert c["vocab_size"] * 4 == c["published"]["vocab_size"] == 151936
    # whole periods in the published order, the reader's name for the window
    layers = c["num_hidden_layers"]
    assert layers % 4 == 0 and layers >= 4
    assert c["rope_layout"] == row["config"]["rope_layout"][:layers]
    assert c["sliding_window_layout"] == row["config"]["sliding_window_layout"][:layers]
    assert c["sliding_window"] == c["sliding_window_size"] == 4096
    assert c["training"]["seq"] == c["max_position_embeddings"] == 16384


def _run(ops, programs=None, busy_s=3.0):
    config = _json(BENCH, "configs", f"{CONFIG}.json")
    cell = types.SimpleNamespace(config=config, chips=1, counts=_counts(config))
    reduced = {
        "busy_s": busy_s, "window_s": busy_s * 1.01, "device_ops": ops[:2],
        "programs": programs or {"step_fn": {"count": 4, "device_s": 2.4}},
    }
    return {
        "cell": cell, "trace": reduced, "peaks_kind": "TPU v5 lite",
        "counts": {"device_ops_all": ops},
    }


def test_full_share_from_a_made_up_reduction():
    from readers import banded_mxu_share, full_mxu_share

    ops = [
        ["step_fn/fusion.1 bf16[16384,2560]", 1.0],
        ["step_fn/flash_bwd_dkv.7 bf16[1,4,16384,128]", 0.5],
        ["step_fn/flash_fwd.3 bf16[1,28,16384,128]", 0.25],
        ["step_fn/flash_bwd_dq.5 bf16[1,28,16384,128]", 0.25],
        ["step_fn/flash_win_fwd.4 bf16[1,28,16384,128]", 9.0],  # a window layer's
        ["step_fn/flash_win_bwd_dq.9 bf16[1,28,16384,128]", 1.0],
        ["other/flash_fwd.3 bf16[1,28,16384,128]", 9.0],
    ]
    run = _run(ops)
    n_full = run["cell"].counts["n_full_layers"]
    need = routed.causal_attention_flops(1, 16384, 28, 128, 128, n_full)
    assert need == pytest.approx(5.773e12 * n_full, rel=1e-3)
    # 3.0 s busy over 0.6 s an execution: five executions share 1.0 s
    share = full_mxu_share.read(run, kernels=["flash_fwd", "flash_bwd_"])
    assert share == pytest.approx(100 * need / 0.2 / 197e12)
    # the banded kernels' time is the other reader's, and the other way
    banded_share = banded_mxu_share.read(run, kernels=["flash_win_"])
    assert banded_share == pytest.approx(
        100 * banded.window_attention_flops(
            1, 16384, 4096, 28, 128, 128, run["cell"].counts["n_window_layers"]
        ) / 2.0 / 197e12
    )
    # nothing to read: a trace without the kernels, a family that counts
    # no full layers (every accepted one), no trace
    plain = _run([op for op in ops if "/flash_fwd" not in op[0] and "flash_bwd" not in op[0]])
    assert full_mxu_share.read(plain, kernels=["flash_fwd", "flash_bwd_"]) is None
    del run["cell"].counts["n_full_layers"]
    assert full_mxu_share.read(run, kernels=["flash_fwd", "flash_bwd_"]) is None
    run["trace"] = None
    assert full_mxu_share.read(run, kernels=["flash_fwd", "flash_bwd_"]) is None


def test_full_share_on_the_recorded_trace():
    """A recorded step of a small GPT-2 on a v5e: its three flash calls
    (``flash_fwd`` twice under remat, ``flash_bwd_fused``) are found by
    name, none of them banded, and their time gives the share."""
    from readers import banded_mxu_share, full_mxu_share

    reduced = trace.reduce(
        os.path.join(BENCH, "fixtures", "scoped_step.xplane.pb"), top=1 << 30
    )
    ops = reduced["device_ops"]
    run = _run(ops, reduced["programs"], reduced["busy_s"])
    run["trace"] = reduced
    seconds = routed.kernel_seconds_per_step(
        reduced, ops, "step_fn", ["flash_fwd", "flash_bwd_"]
    )
    flash = sum(s for k, s in ops if "/flash_" in k)
    executions = reduced["busy_s"] / (
        reduced["programs"]["step_fn"]["device_s"] / reduced["programs"]["step_fn"]["count"]
    )
    assert seconds == pytest.approx(flash / executions) and seconds > 0
    n_full = run["cell"].counts["n_full_layers"]
    assert full_mxu_share.read(
        run, kernels=["flash_fwd", "flash_bwd_"]
    ) == pytest.approx(
        100 * routed.causal_attention_flops(1, 16384, 28, 128, 128, n_full)
        / seconds / 197e12
    )
    assert banded_mxu_share.read(run, kernels=["flash_win_"]) is None


def test_the_cell_lists_what_benchmark_json_lists():
    b = _json(ROOT, "BENCHMARK.json")
    w = _json(BENCH, "workloads", f"{CELL}.json")
    listed = [m["name"] for m in b["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert sorted(w["per_layer"]) == sorted(listed)
    assert w["driver"] == "train_steps_routed_queued" and w["chips"] == 1
    (entry,) = [x for x in b["workloads"] if x["name"] == CELL]
    assert entry["why"] == w["why"] and len(w["why"]) <= 200
    # the new metrics read this cell and no other
    new = [
        m for m in b["per_layer"]
        if m["name"].startswith("smallthinker_") or m["name"] == "full_flash_mxu_pct"
    ]
    assert len(new) == 6 and all(m["workloads"] == [CELL] for m in new)
