"""The yardstick of the window/full-attention cell: counts from shapes
against hand-worked values, the banded kernels' required operations, and
the reader of their share on a made-up reduction."""

import importlib
import json
import os
import types

import pytest

from benchlib import banded, routed, shapes
from conftest import BENCH, ROOT

CONFIG = "trinity-mini"
CELL = f"{CONFIG}.pretrain-8k"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("seq,window", [(8192, 2048), (100, 7), (5, 9), (64, 64)])
def test_window_pairs_are_the_masks(seq, window):
    pairs = sum(
        1 for t in range(seq) for j in range(seq) if 0 <= t - j < window
    ) if seq <= 100 else 2048 * 2049 // 2 + 6144 * 2048
    assert banded.window_pairs(seq, window) == pairs
    if window >= seq:
        assert pairs == seq * (seq + 1) // 2


def test_counts_by_hand():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    n = importlib.import_module("families.afmoe").counts(c)
    # W_q, the gate's and W_o 2048 x 4096 each; W_k and W_v 2048 x 512
    attn = 3 * 8_388_608 + 2 * 1_048_576
    assert n["attn_params"] == attn == 27_262_976
    expert = 3 * 2048 * 1024
    assert n["expert_params"] == expert == 6_291_456
    # router 2048 x 128; one shared expert; 8 x 32/128 routed in expectation
    per_moe = attn + 262_144 + expert + 8 * expert // 4
    per_dense = attn + 3 * 2048 * 6144
    assert n["matmul_params"] == per_dense + 4 * per_moe + 50048 * 2048
    assert n["matmul_params"] == 353_107_968
    assert (n["n_layers"], n["n_moe_layers"], n["n_window_layers"]) == (5, 4, 4)
    # four window layers' pairs and one triangle, over five triangles
    window, triangle = 14_681_088, 33_558_528
    assert banded.window_pairs(8192, 2048) == window
    assert n["d_attn"] == 4096 * (4 * window + triangle) // (5 * triangle) == 2252
    # what train_mfu_pct charges attention is no more than what is required
    charged = 6 * n["d_attn"] * 8193 * 5 * 8192
    required = (
        banded.window_attention_flops(1, 8192, 2048, 32, 128, 128, 4)
        + routed.causal_attention_flops(1, 8192, 32, 128, 128, 1)
    )
    assert 0.999 * required < charged <= required
    assert shapes.train_flops_per_token(
        n["matmul_params"], 5, n["d_attn"], 8192
    ) * 8192 == pytest.approx(21.9e12, rel=0.01)


def test_the_file_holds_the_published_config_and_the_share():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    assert row["source_url"] == c["source"]
    differ = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differ == set(c["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size",
    }
    (entry,) = [
        x for x in _json(ROOT, "BENCHMARK.json")["configs"] if x["name"] == CONFIG
    ]
    assert set(entry["reduced"]) == differ and entry["source"] == c["source"]
    assert c["published"]["num_experts"] == c["num_experts_total"] == 128


def _run(ops):
    config = _json(BENCH, "configs", f"{CONFIG}.json")
    cell = types.SimpleNamespace(
        config=config, chips=1,
        counts=importlib.import_module("families.afmoe").counts(config),
    )
    trace = {
        "busy_s": 3.0, "window_s": 3.05, "device_ops": ops[:2],
        "programs": {"step_fn": {"count": 4, "device_s": 2.4}},
    }
    return {
        "cell": cell, "trace": trace, "peaks_kind": "TPU v5 lite",
        "counts": {"device_ops_all": ops},
    }


def test_banded_share_from_a_made_up_reduction():
    from readers import banded_mxu_share, hbm_kernel_share

    ops = [
        ["step_fn/fusion.1 bf16[8192,2048]", 1.0],
        ["step_fn/flash_win_bwd_dkv.7 bf16[1,4,8192,128]", 0.5],
        ["step_fn/flash_win_fwd.3 bf16[1,32,8192,128]", 0.25],
        ["step_fn/flash_win_bwd_dq.5 bf16[1,32,8192,128]", 0.25],
        ["step_fn/flash_fwd.4 bf16[1,32,8192,128]", 9.0],  # the full layer's
        ["other/flash_win_fwd.3 bf16[1,32,8192,128]", 9.0],
    ]
    run = _run(ops)
    # 3.0 s busy over 0.6 s an execution: five executions share 1.0 s
    need = banded.window_attention_flops(1, 8192, 2048, 32, 128, 128, 4)
    assert need == pytest.approx(2.886e12, rel=1e-3)
    share = banded_mxu_share.read(run, kernels=["flash_win_"])
    assert share == pytest.approx(100 * need / 0.2 / 197e12)
    assert hbm_kernel_share.read(run, kernels=["flash_win_"]) == pytest.approx(200.0)
    # nothing to read: a trace without the kernels (the parent's), no trace
    plain = _run([op for op in ops if "flash_win_" not in op[0]])
    assert banded_mxu_share.read(plain, kernels=["flash_win_"]) is None
    run["trace"] = None
    assert banded_mxu_share.read(run, kernels=["flash_win_"]) is None


def test_the_cell_lists_what_benchmark_json_lists():
    b = _json(ROOT, "BENCHMARK.json")
    w = _json(BENCH, "workloads", f"{CELL}.json")
    listed = [m["name"] for m in b["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert sorted(w["per_layer"]) == sorted(listed)
    assert w["driver"] == "train_steps_routed_queued" and w["chips"] == 1
    (entry,) = [x for x in b["workloads"] if x["name"] == CELL]
    assert entry["why"] == w["why"]
