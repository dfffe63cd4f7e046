"""The yardstick of the latent-attention, routed-expert cell: counts from
shapes against hand-worked values, the kernels' required operations, and
the readers of their shares on a made-up reduction."""

import importlib
import json
import os
import types

import pytest

from benchlib import routed, shapes
from conftest import BENCH, ROOT

CONFIG = "kanana-2-30b-a3b-instruct-2601"
CELL = f"{CONFIG}.pretrain-8k"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def test_counts_by_hand():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    n = importlib.import_module("families.deepseek_v3").counts(c)
    # W_q 2048 x 32 x 192, W_kva 2048 x 576, W_kvb 512 x 32 x 256, W_o 4096 x 2048
    attn = 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    assert n["attn_params"] == attn == 26_345_472
    expert = 3 * 2048 * 768
    assert n["expert_params"] == expert == 4_718_592
    # router 2048 x 128; two shared experts; 6 x 32/128 routed in expectation
    per_moe = attn + 262_144 + 2 * expert + 6 * expert // 4
    per_dense = attn + 3 * 2048 * 6144
    assert n["matmul_params"] == per_dense + 5 * per_moe + 32064 * 2048
    assert n["matmul_params"] == 345_374_720
    assert (n["n_layers"], n["n_moe_layers"], n["d_attn"]) == (6, 5, 5120)
    flops = shapes.train_flops_per_token(
        n["matmul_params"], n["n_layers"], n["d_attn"], c["training"]["seq"]
    )
    assert flops == 6 * 345_374_720 + 6 * 5120 * 8193 * 6
    # the attention term, for the whole step, is the kernels' required work
    assert routed.causal_attention_flops(1, 8192, 32, 192, 128, 6) == (
        6 * 5120 * 8193 * 6 * 8192
    )
    assert routed.expert_flops(12288, expert) == 6 * expert * 12288


def test_the_file_holds_the_published_config_and_the_share():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    cat = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(cat):
        pytest.skip("no catalog here")
    with open(cat) as f:
        row = next(
            r for r in map(json.loads, f) if r["name"] == CONFIG
        )
    assert row["source_url"] == c["source"]
    differ = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differ == set(c["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"
    }
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert c["n_routed_experts_total"] == 128 == 4 * c["n_routed_experts"]
    assert c["vocab_size"] * 4 == 128256 and c["num_experts_per_tok"] == 6
    b = _json(ROOT, "BENCHMARK.json")
    entry = next(x for x in b["configs"] if x["name"] == CONFIG)
    assert entry["source"] == c["source"]


def _run(ops, assignments=12288.0):
    config = _json(BENCH, "configs", f"{CONFIG}.json")
    cell = types.SimpleNamespace(
        config=config, chips=1,
        counts=importlib.import_module("families.deepseek_v3").counts(config),
    )
    trace = {
        "busy_s": 3.0, "window_s": 3.05, "device_ops": ops[:2],
        "programs": {"step_fn": {"count": 4, "device_s": 2.4}},
    }
    return {
        "cell": cell, "trace": trace, "peaks_kind": "TPU v5 lite",
        "counts": {"local_assignments_per_step": assignments,
                   "device_ops_all": ops},
    }


def test_kernel_shares_from_a_made_up_reduction():
    from readers import mxu_share

    ops = [
        ["step_fn/fusion.1 bf16[8192,2048]", 1.0],
        ["step_fn/flash_bwd_dkv.7 bf16[1,32,8192,192]", 0.5],
        ["step_fn/flash_fwd.3 bf16[1,32,8192,128]", 0.25],
        ["step_fn/flash_bwd_dq.5 bf16[1,32,8192,192]", 0.25],
        ["step_fn/ragged-dot-none.2 bf16[49152,768]", 0.2],
        ["step_fn/ragged-dot-none.11 bf16[32,2048,768]", 0.05],
        ["other/flash_fwd.3 bf16[1,32,8192,128]", 9.0],
        ["step_fn/copy.181 f32[1,32,8192]", 9.0],
    ]
    run = _run(ops)
    # 3.0 s busy / 0.6 s a step = 5 executions, the cut ones included
    assert routed.kernel_seconds_per_step(
        run["trace"], ops, "step_fn", ["flash_fwd", "flash_bwd_"]
    ) == pytest.approx(1.0 / 5)
    flash = mxu_share.read(
        run, kernels=["flash_fwd", "flash_bwd_"], flops="causal_attention"
    )
    assert flash == pytest.approx(
        100 * 6 * 5120 * 8193 * 6 * 8192 / 0.2 / 197e12
    )
    experts = mxu_share.read(run, kernels=["ragged-dot"], flops="experts")
    assert experts == pytest.approx(
        100 * 6 * 4_718_592 * 12288 / 0.05 / 197e12
    )
    # without the driver's full list: the ten largest (here two) rows
    del run["counts"]["device_ops_all"]
    assert mxu_share.read(
        run, kernels=["ragged-dot"], flops="experts"
    ) is None
    assert mxu_share.read(
        run, kernels=["flash_fwd", "flash_bwd_"], flops="causal_attention"
    ) == pytest.approx(flash * 1.0 / 0.5)
    # nothing to read: no trace, or a program that is not in it
    run["trace"]["programs"] = {}
    assert mxu_share.read(run, kernels=["flash_fwd"], flops="causal_attention") is None
    run["trace"] = None
    assert mxu_share.read(run, kernels=["flash_fwd"], flops="causal_attention") is None


def test_the_cell_lists_what_benchmark_json_lists():
    b = _json(ROOT, "BENCHMARK.json")
    w = _json(BENCH, "workloads", f"{CELL}.json")
    listed = [m["name"] for m in b["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert sorted(w["per_layer"]) == sorted(listed)
    assert w["driver"] == "train_steps_routed" and w["chips"] == 1


def test_gradient_gaps_and_their_limits():
    import jax.numpy as jnp
    import numpy as np

    from drivers import train_steps_routed as driver

    ref = {"embed": {"weight": jnp.array([[3.0, 4.0], [0.0, 5.0], [6.0, 8.0]])},
           "layer": {"e_up": jnp.array([[[1.0, 0.0]], [[0.0, 2.0]]]),
                     "bias": jnp.zeros(2)}}
    cell = types.SimpleNamespace(
        config={"tol": {"gradient": 0.3, "gradient_rows": 0.05}}
    )
    tokens = np.array([[2, 0, 2]])
    same = driver.compare(ref, ref, tokens)
    assert set(same["gaps"]) == {
        "['embed']['weight']", "['layer']['bias']", "['layer']['e_up']",
        "['layer']['e_up'][0]", "['layer']['e_up'][1]",
    }
    assert set(same["gaps"].values()) == {0.0}
    assert same["rows"].tolist() == [0.0, 0.0]  # the batch's rows: 0 and 2
    assert driver.gradient_ok(cell, same)[0]
    # a state left unchanged gives no gradient: every moved part reads 1
    still = driver.compare(jax_zeros(ref), ref, tokens)
    assert still["gaps"]["['layer']['bias']"] == 0.0
    assert {v for k, v in still["gaps"].items() if "bias" not in k} == {1.0}
    assert still["rows"].tolist() == [1.0, 1.0]
    assert not driver.gradient_ok(cell, still)[0]
    # an expert the routed sum skips: its slice reads 1, the leaf less
    skipped = dict(ref, layer=dict(ref["layer"], e_up=ref["layer"]["e_up"].at[0].set(0)))
    reading = driver.compare(skipped, ref, tokens)
    assert reading["gaps"]["['layer']['e_up'][0]"] == 1.0
    assert reading["gaps"]["['layer']['e_up']"] == pytest.approx(5 ** -0.5)
    ok, detail = driver.gradient_ok(cell, reading)
    assert not ok and "1.00000 at ['layer']['e_up'][0]" in detail
    # rows: the median decides, so one row far off passes and two do not
    off = lambda rows: dict(ref, embed={"weight": ref["embed"]["weight"] * jnp.array(rows)[:, None]})
    one = driver.compare(off([1.0, 1.0, 1.08]), ref, np.array([[0, 1, 2]]))
    assert one["rows"].tolist() == pytest.approx([0.0, 0.0, 0.08])
    assert driver.gradient_ok(cell, one)[0]
    two = driver.compare(off([1.0, 1.08, 1.08]), ref, np.array([[0, 1, 2]]))
    ok, detail = driver.gradient_ok(cell, two)
    assert not ok and "median of the embedding's 3 rows 0.08000" in detail
    # a part the reference leaves alone has to be left alone; NaN fails
    moved = dict(ref, layer=dict(ref["layer"], bias=jnp.ones(2)))
    assert driver.compare(moved, ref, tokens)["gaps"]["['layer']['bias']"] == float("inf")
    assert not driver.gradient_ok(cell, driver.compare(moved, ref, tokens))[0]
    nan = dict(same, gaps=dict(same["gaps"], **{"['layer']['e_up']": float("nan")}))
    assert not driver.gradient_ok(cell, nan)[0]


def jax_zeros(tree):
    import jax

    return jax.tree.map(lambda a: a * 0, tree)


@pytest.mark.parametrize("fault", ["skip_expert", "fp8_experts", "fp8_attention"])
def test_a_faulted_program_is_not_correct(fault):
    """The whole harness at the ``tiny`` block with the fault in the
    program: every loss is finite, and the run is not correct."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "reference", "measure_tol_gradient.py"),
         "--workload", CELL, "--fault", fault, "--seed", str(2**31 + 7),
         "--rehearse"],
        capture_output=True, text=True, env=env, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    check = next(x for x in p.stdout.splitlines() if x.startswith("reference check"))
    assert "all" in check and "losses finite: True" in check
