"""The yardstick of the Olmo-Hybrid cell: the delta rule's bytes from shapes
(``benchlib/delta.py``), the family's counts, the file against the
catalog's row, and the kernel readers on a made-up reduction."""

import importlib
import json
import os
import types

import pytest

from benchlib import delta, shapes
from conftest import BENCH, ROOT

CONFIG = "olmo-hybrid-7b"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_the_cells_bytes():
    """Forward q, k, v, g, beta in and o out; backward those, do, and five
    gradients: six ``(T, H, d_k)``, five ``(T, H, d_v)`` bf16 arrays and six
    float32 gates a layer, about 4.6 GB over three layers, 5.6 ms at
    819 GB/s."""
    b = delta.delta_rule_bytes(1, 16384, 30, 96, 192, 3, 2)
    assert b == 3 * 16384 * 30 * (2 * (6 * 96 + 5 * 192) + 6 * 4)
    assert 4.5e9 < b < 4.6e9 and 5.5e-3 < b / 819e9 < 5.7e-3
    assert delta.delta_rule_bytes(2, 100, 4, 8, 16, 1, 4) == 2 * 100 * 4 * (
        4 * (6 * 8 + 5 * 16) + 24
    )


def test_the_family_counts():
    """The FLOPs of one step: the layers' matrices 81.8 TFLOP, the head
    18.9, the full layer's causal pairs 3 x 2.06, the delta rule's
    recurrent form under 1."""
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    n = importlib.import_module("families.olmo_hybrid").counts(c)
    assert (n["n_full_layers"], n["n_linear_layers"], n["n_layers"]) == (1, 3, 4)
    layers = n["matmul_params"] - c["vocab_size"] * c["hidden_size"]
    assert layers == 3 * (88_704_000 + 126_812_160) + 58_982_400 + 126_812_160
    seq = c["training"]["seq"]
    assert 81.7e12 < 6 * layers * seq < 81.9e12
    assert 18.8e12 < 6 * c["vocab_size"] * c["hidden_size"] * seq < 19.0e12
    per_token = shapes.train_flops_per_token(
        n["matmul_params"], n["n_layers"], n["d_attn"], seq
    )
    attn = per_token * seq - 6 * n["matmul_params"] * seq
    full = 3 * 2 * 2 * c["hidden_size"] * seq * (seq + 1) // 2
    recurrent = 3 * 3 * 6 * 96 * 192 * 30 * seq
    assert 0.999 < attn / (full + recurrent) <= 1.0
    assert 106e12 < per_token * seq < 108e12


def test_the_file_keeps_the_catalogs_numbers():
    """Every number of the catalog's row at the top level, under its key,
    changed only where ``reduced`` names it; nested groups copied whole."""
    path = os.environ.get("MODEL_CATALOG", "")  # the catalog's architectures.jsonl
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Olmo-Hybrid-7B")
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    bench = _json(ROOT, "BENCHMARK.json")
    (entry,) = [x for x in bench["configs"] if x["name"] == CONFIG]
    assert entry["source"] == row["source_url"] == c["source"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key
    assert c["layer_types"] == row["config"]["layer_types"][:4]
    assert set(entry["reduced"]) == set(c["reduced"])
    (cell,) = [w for w in bench["workloads"] if w["config"] == CONFIG]
    assert cell["chips"] == 1 and len(cell["why"]) <= 200


def _run(ops):
    config = _json(BENCH, "configs", f"{CONFIG}.json")
    counts = importlib.import_module("families.olmo_hybrid").counts(config)
    cell = types.SimpleNamespace(config=config, chips=1, counts=counts, itemsize=2)
    trace = {
        "programs": {"step_fn": {"count": 1, "device_s": 1.0}},
        "busy_s": 1.0, "device_ops": ops,
    }
    return {"cell": cell, "counts": {}, "trace": trace, "peaks_kind": "TPU v5 lite"}


def test_readers_on_a_made_up_reduction():
    """50 ms in the delta rule's kernels, one execution: the share of the
    bytes' roofline and the milliseconds; nothing where no kernel ran."""
    share = importlib.import_module("readers.delta_rule_hbm_share")
    ms = importlib.import_module("readers.hbm_kernel_share")
    ops = [
        ["step_fn/delta_rule_fwd.1 bf16[1,30,16384,192]", 0.02],
        ["step_fn/delta_rule_bwd.1 bf16[1,30,16384,96]", 0.03],
        ["step_fn/flash_fwd.2 bf16[1,30,16384,128]", 0.04],
        ["step_fn/fusion.1 bf16[16384,11008]", 0.5],
    ]
    run = _run(ops)
    need = delta.delta_rule_bytes(1, 16384, 30, 96, 192, 3, 2)
    assert share.read(run, ["delta_rule_"]) == pytest.approx(100 * need / 0.05 / 819e9)
    assert ms.read(run, ["delta_rule_"], bytes=None) == pytest.approx(50.0)
    assert share.read(_run(ops[2:]), ["delta_rule_"]) is None
    assert share.read(dict(run, trace=None), ["delta_rule_"]) is None
