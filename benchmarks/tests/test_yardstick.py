"""The yardstick's arithmetic: order statistics, traffic grids, FLOPs and
bytes from shapes against hand-worked values, the peaks table, and the
consistency of BENCHMARK.json with the files it names."""

import json
import os

import numpy as np
import pytest

from benchlib import peaks, shapes, stats, traffic
from conftest import BENCH, ROOT


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _counts(config):
    import importlib

    c = _json(BENCH, "configs", f"{config}.json")
    return c, importlib.import_module(f"families.{c['family']}").counts(c)


def test_failed_request_ranks_above_finished():
    # Nine finished in 1..9 s and one unfinished that had waited only 0.5 s
    # when the run gave up: the 95th percentile is the failure, not 9 s.
    vals = [float(i) for i in range(1, 10)] + [0.5]
    failed = [False] * 9 + [True]
    assert stats.percentile(vals, failed, 95) == 0.5
    assert stats.percentile(vals, failed, 90) == 9.0
    assert stats.percentile(vals, failed, 50) == 5.0
    assert stats.percentile([], [], 90) is None


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 7])
def test_grids_hold_the_same_work_in_any_order(seed):
    spec = {"dist": "lognormal", "median": 128, "sigma": 0.8, "lo": 16, "hi": 640}
    base = traffic.grid(spec, 40)
    assert base.min() >= 16 and base.max() <= 640 and base.dtype == np.int64
    assert abs(float(np.median(base)) - 128) < 6
    rng = np.random.default_rng(seed)
    assert sorted(rng.permutation(base)) == sorted(base)
    offs = traffic.arrivals(rng, 0.85, 50.0, {"dist": "exponential"})
    assert len(offs) == round(0.85 * 50) and 0 < offs[0] and offs[-1] < 50
    assert (np.diff(offs) > 0).all()
    assert _gaps(offs).sum() == pytest.approx(50.0)
    other = traffic.arrivals(
        np.random.default_rng(seed + 1), 0.85, 50.0, {"dist": "exponential"}
    )
    assert np.sort(_gaps(offs)) == pytest.approx(np.sort(_gaps(other)))
    assert not np.allclose(offs, other)


def _gaps(offs):
    """Each arrival sits in the middle of its gap: undo that."""
    gaps, edge = [], 0.0
    for o in offs:
        gaps.append(2 * (o - edge))
        edge += gaps[-1]
    return np.array(gaps)


def test_the_schedule_is_the_cells_and_the_seed_draws_the_tokens():
    import run as bench
    from drivers import closed_loop, open_loop

    a, b = (
        open_loop.plan(bench.load_cell("gpt2-xl.chat", s, True), 5.0, 6.0)
        for s in (1, 2)
    )
    assert [(x[0], x[1], len(x[2]), x[3]) for x in a] == [
        (x[0], x[1], len(x[2]), x[3]) for x in b
    ]
    assert any((x[2] != y[2]).any() for x, y in zip(a, b))
    docs = [
        closed_loop.requests(bench.load_cell("mistral-7b-v0.3.docs", s, True))
        for s in (1, 2)
    ]
    for _ in range(40):
        (p1, m1), (p2, m2) = next(docs[0]), next(docs[1])
        assert (len(p1), m1) == (len(p2), m2) and (p1 != p2).any()


def test_gamma_gaps_are_burstier_than_exponential():
    e = traffic.grid({"dist": "exponential", "int": False}, 400)
    g = traffic.grid({"dist": "gamma", "cv": 2.0, "int": False}, 400)
    assert np.std(g) / np.mean(g) > 1.5 > np.std(e) / np.mean(e) > 0.8


def test_zipf_block_counts():
    b = traffic.zipf_block(16, 1.0, 64)
    counts = np.bincount(b, minlength=16)
    assert len(b) == 64 and counts[0] == 19 and counts[-1] >= 1
    assert (np.diff(counts) <= 0).all()


def test_gpt2_xl_counts_by_hand():
    c, n = _counts("gpt2-xl")
    # per layer 12 * 1600^2 = 30,720,000; x48 = 1,474,560,000; tied head
    # 50257 * 1600 = 80,411,200.
    assert n["matmul_params"] == 1_474_560_000 + 80_411_200
    # + per layer 14,400 biases and 6,400 norm values; + ln_f 3,200.
    assert n["decode_read_params"] == 48 * 30_740_800 + 80_411_200 + 3_200
    # all but the 1024 x 1600 position table, which a step reads 8 rows of
    assert n["decode_read_params"] + 1024 * 1600 == 1_557_611_200
    assert n["kv_per_position"] == 2 * 48 * 25 * 64
    flops = shapes.train_flops_per_token(
        n["matmul_params"], n["n_layers"], n["d_attn"], c["training"]["seq"]
    )
    assert flops == 6 * 1_554_971_200 + 6 * 1600 * 1025 * 48 == 9_802_147_200
    assert shapes.decode_bytes_per_step(
        n["decode_read_params"] * 2, n["kv_per_position"] * 2, 8 * 500
    ) == 3_111_945_600 + 307_200 * 4000


def test_mistral_12_layer_counts_by_hand():
    c, n = _counts("mistral-7b-v0.3")
    per_layer = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336
    assert per_layer == 218_103_808
    assert n["matmul_params"] == 12 * per_layer + 32768 * 4096
    assert n["decode_read_params"] == 12 * (per_layer + 8192) + 134_217_728 + 4096
    # with the embedding table the model holds 2,885.8M parameters
    assert n["decode_read_params"] + 32768 * 4096 == 2_885_783_552
    assert n["kv_per_position"] * 2 == 49_152
    assert c["num_hidden_layers"] == 12 and "num_hidden_layers" in c["reduced"]


def test_peaks_table_raises_on_an_unknown_device():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks recorded"):
        peaks.peaks("cpu")


def test_benchmark_json_names_files_that_exist_and_agree():
    b = _json(ROOT, "BENCHMARK.json")
    assert set(b) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    configs = {c["name"]: c for c in b["configs"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    layer = {m["name"]: m for m in b["per_layer"]}
    for c in b["configs"]:
        f = _json(ROOT, c["file"])
        assert sorted(f["reduced"]) == sorted(c["reduced"])
        assert c["source"].split(" ")[0] in f["source"]
    for w in b["workloads"]:
        f = _json(BENCH, "workloads", f"{w['name']}.json")
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        assert f["config"] == w["config"] in configs
        assert f["chips"] == w["chips"] and f["why"] == w["why"]
        for kind, table in (("end_to_end", e2e), ("per_layer", layer)):
            for name in f[kind]:
                m = table[name]
                assert w["name"] in m.get("workloads", [w["name"]]), name
                spec = _json(BENCH, "metrics", f"{name}.json")
                assert spec["unit"] == m["unit"]
                assert os.path.exists(
                    os.path.join(BENCH, "readers", spec["reader"] + ".py")
                )
        assert "setup_s" in f["end_to_end"] and len(f["end_to_end"]) >= 2
        for name in f["per_layer"]:
            assert layer[name]["moves"] in f["end_to_end"], name
    for m in list(e2e.values()) + list(layer.values()):
        for w in m.get("workloads", []):
            f = _json(BENCH, "workloads", f"{w}.json")
            assert m["name"] in f["end_to_end"] + f["per_layer"]
