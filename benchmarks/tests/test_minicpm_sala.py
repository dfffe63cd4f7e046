"""The yardstick of the MiniCPM-SALA cell: the pairs and bytes from shapes
(``benchlib/sparse.py``) against brute-force counts, the file against the
catalog's row, and the two kernel readers on a made-up reduction."""

import importlib
import json
import os
import types

import numpy as np
import pytest

from benchlib import sparse
from conftest import BENCH, ROOT

CONFIG = "minicpm-sala"
CELL = f"{CONFIG}.pretrain-16k"


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("seq,block,topk,dense", [
    (256, 8, 6, 32), (200, 8, 6, 32), (64, 8, 6, 64), (1024, 16, 8, 128),
])
def test_chosen_pairs_brute_force(seq, block, topk, dense):
    """Token by token: the keys ``j <= t`` inside ``min(topk, its causal
    blocks)`` blocks of which one is its own partial block."""
    want = 0
    for t in range(seq):
        if seq <= dense:
            want += t + 1
            continue
        own = t // block
        full = min(topk, own + 1) - 1  # whole blocks before its own
        want += full * block + t % block + 1
    assert sparse.chosen_pairs(seq, block, topk, dense) == want


def test_chosen_pairs_are_what_the_program_keeps():
    """The program's own choice on random q and k keeps exactly the pairs
    the count from shapes gives (every token takes its full ``topk``)."""
    import jax
    import jax.numpy as jnp

    from torchdistx_tpu.ops import sparse_attention as sa

    cfg = sa.SparseConfig(
        kernel_size=4, kernel_stride=2, init_blocks=1, block_size=8,
        window_size=16, topk=6, dense_len=32,
    )
    kq, kk = jax.random.split(jax.random.PRNGKey(0))
    q = jax.random.normal(kq, (1, 256, 4, 16))
    k = jax.random.normal(kk, (1, 256, 2, 16))
    chosen = np.asarray(sa.unpack_blocks(sa.select_blocks(q, k, cfg), 32))
    keys = np.repeat(chosen, 8, axis=-1) & np.tril(np.ones((256, 256), bool))
    assert (keys.sum((-1, -2)) == sparse.chosen_pairs(256, 8, 6, 32)).all()


def test_the_cells_counts():
    """At 16,384 positions a token past 4,096 keeps 63 whole blocks and its
    own: 43% of the causal pairs; the FLOPs of one sparse layer."""
    pairs = sparse.chosen_pairs(16384, 64, 64, 8192)
    causal = 16384 * 16385 // 2
    assert pairs == 4096 * 4097 // 2 + sum(
        63 * 64 + t % 64 + 1 for t in range(4096, 16384)
    )
    assert 0.43 < pairs / causal < 0.44 and 58.2e6 < pairs < 58.4e6
    assert sparse.sparse_attention_flops(1, 16384, 64, 64, 8192, 32, 128, 128, 1) == (
        6 * 256 * 32 * pairs
    )
    # eleven (T, 4096) bf16 arrays a layer: about 4.4 GB a step, 5.4 ms
    b = sparse.lightning_bytes(1, 16384, 32, 128, 3, 2)
    assert b == 11 * 16384 * 4096 * 2 * 3
    assert 5.3e-3 < b / 819e9 < 5.5e-3


@pytest.mark.parametrize("seq,chunk", [(64, 16), (70, 16), (16384, 256)])
def test_chunk_pairs_brute_force(seq, chunk):
    want = sum(t % chunk + 1 for t in range(seq))
    assert sparse.chunk_pairs(seq, chunk) == want


def test_the_file_keeps_the_catalogs_numbers():
    """Every number of the catalog's row at the top level, under its key,
    changed only where ``reduced`` names it."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(path):
        pytest.skip("no catalog here")
    with open(path) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "MiniCPM-SALA")
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    bench = _json(ROOT, "BENCHMARK.json")
    (entry,) = [x for x in bench["configs"] if x["name"] == CONFIG]
    assert entry["source"] == row["source_url"] == c["source"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key
    assert c["mixer_types"] == row["config"]["mixer_types"][:4]
    assert set(entry["reduced"]) == set(c["reduced"])


def _run(ops, config=None):
    cell_config = config or _json(BENCH, "configs", f"{CONFIG}.json")
    counts = importlib.import_module("families.minicpm_sala").counts(cell_config)
    cell = types.SimpleNamespace(
        config=cell_config, chips=1, counts=counts, itemsize=2,
    )
    trace = {
        "programs": {"step_fn": {"count": 1, "device_s": 1.0}},
        "busy_s": 1.0, "device_ops": ops,
    }
    return {
        "cell": cell, "counts": {}, "trace": trace, "peaks_kind": "TPU v5 lite",
    }


def test_readers_on_a_made_up_reduction():
    """100 ms in the sparse kernels and 10 ms in the lightning ones, one
    execution: the shares from the counts above; nothing of a kernel not
    in the trace."""
    mxu = importlib.import_module("readers.sparse_mxu_share")
    hbm = importlib.import_module("readers.lightning_hbm_share")
    ops = [
        ["step_fn/sparse_flash_fwd.2 bf16[1,16384,4096]", 0.04],
        ["step_fn/sparse_flash_bwd_dkv.2 bf16[1,16384,256]", 0.06],
        ["step_fn/lightning_fwd.1 bf16[1,16384,4096]", 0.004],
        ["step_fn/lightning_bwd.1 bf16[1,16384,4096]", 0.006],
        ["step_fn/fusion.1 bf16[16384,16384]", 0.5],
    ]
    run = _run(ops)
    flops = sparse.sparse_attention_flops(1, 16384, 64, 64, 8192, 32, 128, 128, 1)
    assert mxu.read(run, ["sparse_flash_"]) == pytest.approx(
        100 * flops / 0.1 / 197e12
    )
    assert hbm.read(run, ["lightning_"]) == pytest.approx(
        100 * sparse.lightning_bytes(1, 16384, 32, 128, 3, 2) / 0.01 / 819e9
    )
    assert mxu.read(_run(ops[2:]), ["sparse_flash_"]) is None
    assert hbm.read(dict(run, trace=None), ["lightning_"]) is None


def test_the_family_counts():
    c = _json(BENCH, "configs", f"{CONFIG}.json")
    n = importlib.import_module("families.minicpm_sala").counts(c)
    assert (n["n_sparse_layers"], n["n_lightning_layers"], n["n_layers"]) == (1, 3, 4)
    # projections and gates of the four layers, feed-forward, head
    assert n["matmul_params"] == (
        52_428_800 + 201_326_592 + 3 * (83_886_080 + 201_326_592) + 18362 * 4096
    )
    assert 0 < n["d_attn"] < 32 * 128
