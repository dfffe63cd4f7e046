"""JAX materialization tests: the shard-then-materialize path.

Runs on a virtual 8-device CPU mesh (conftest.py) — the analog of the
reference's single-host multi-GPU FSDPTest trick (SURVEY.md §4)."""

import numpy as np
import pytest
import torch
import torch.nn as nn

import torchdistx_tpu.deferred_init as di
from torchdistx_tpu import fake
from torchdistx_tpu.materialize import (
    materialize_module_jax,
    materialize_tensor_jax,
)
from torchdistx_tpu.parallel import (
    MeshSpec,
    combine_plans,
    fsdp_plan,
    fsdp_over,
    make_mesh,
    tp_plan_gpt2,
    tp_plan_llama,
)


def test_materialize_tensor_jax_values():
    with di._deferred_init_context():
        t = torch.zeros(4, 4)
        t.add_(1)
        t.mul_(3)
    arr = materialize_tensor_jax(t)
    np.testing.assert_allclose(np.asarray(arr), np.full((4, 4), 3.0))


def test_materialize_linear_statistics():
    m = di.deferred_init(nn.Linear, 128, 64)
    out = materialize_module_jax(m)
    assert set(out) == {"weight", "bias"}
    w = np.asarray(out["weight"])
    assert w.shape == (64, 128)
    bound = (1 / 128) ** 0.5 * (3**0.5)
    assert np.abs(w).max() <= bound + 1e-6
    assert w.std() > 0.5 * bound / (3**0.5)  # roughly uniform spread


def test_jax_path_view_and_inplace():
    with di._deferred_init_context():
        base = torch.zeros(2, 4)
        row = base[1]
        row.fill_(7)
        base.mul_(2)
    arr = materialize_tensor_jax(base)
    np.testing.assert_allclose(
        np.asarray(arr), [[0.0] * 4, [14.0] * 4]
    )


def test_jax_matches_torch_replay_for_deterministic_ops():
    def build():
        t = torch.arange(12.0).view(3, 4)
        u = (t * 2).t()
        return nn.Parameter(u.contiguous())

    with di._deferred_init_context():
        p = build()
    arr = materialize_tensor_jax(p)
    ref = di.materialize_tensor(p)
    np.testing.assert_allclose(np.asarray(arr), ref.detach().numpy())


def test_sharded_materialization_fsdp():
    mesh = make_mesh(MeshSpec(fsdp=8))
    m = di.deferred_init(nn.Linear, 256, 128)
    out = materialize_module_jax(m, mesh=mesh, plan=fsdp_plan())
    w = out["weight"]
    assert w.shape == (128, 256)
    # Sharded along the largest dim (256 = dim 1) over 8 devices.
    assert len(w.sharding.device_set) == 8
    shard_shapes = {s.data.shape for s in w.addressable_shards}
    assert shard_shapes == {(128, 32)}
    # Bias is small -> replicated.
    assert out["bias"].sharding.is_fully_replicated


def test_sharded_values_match_unsharded():
    mesh = make_mesh(MeshSpec(fsdp=8))
    m = di.deferred_init(nn.Linear, 64, 32)
    sharded = materialize_module_jax(m, mesh=mesh, plan=fsdp_plan(min_size=1))
    unsharded = materialize_module_jax(m)
    for k in sharded:
        np.testing.assert_allclose(
            np.asarray(sharded[k]), np.asarray(unsharded[k]), rtol=1e-6
        )


def test_replicate_mesh_args_places_explicitly():
    """VERDICT item 8b: mesh-job argument leaves are handed to compiled
    executables as explicitly mesh-replicated arrays — never as raw host
    numpy relying on Compiled.__call__'s version-dependent tolerance."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    from torchdistx_tpu.materialize import _replicate_mesh_args

    mesh = make_mesh(MeshSpec(fsdp=8))
    args = [
        (np.arange(6, dtype=np.uint32), [np.ones((2, 3), np.float32)]),
        (np.float64(2.5), 7),  # non-array leaves pass through untouched
    ]
    placed = _replicate_mesh_args(args, mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    a0, (a1,) = placed[0]
    for arr, src in ((a0, args[0][0]), (a1, args[0][1][0])):
        assert isinstance(arr, jax.Array)
        assert arr.sharding.is_equivalent_to(rep, arr.ndim)
        np.testing.assert_array_equal(np.asarray(arr), src)
    assert placed[1] == args[1]


def test_sharded_mesh_jobs_fed_replicated_inputs():
    """End-to-end: a mesh materialization routes its rest-job args
    through _replicate_mesh_args (values already pinned by
    test_sharded_values_match_unsharded; this pins the placement)."""
    import torchdistx_tpu.materialize as mz

    mesh = make_mesh(MeshSpec(fsdp=8))
    seen = []
    orig = mz._replicate_mesh_args

    def spy(all_args, m):
        out = orig(all_args, m)
        seen.append(out)
        return out

    mz._replicate_mesh_args = spy
    try:
        m = di.deferred_init(nn.Linear, 64, 32)
        materialize_module_jax(m, mesh=mesh, plan=fsdp_plan(min_size=1))
    finally:
        mz._replicate_mesh_args = orig
    assert seen, "mesh run never placed its job args explicitly"


def test_tp_plan_gpt2_specs():
    plan = tp_plan_gpt2()
    assert tuple(plan("transformer.h.0.attn.c_attn.weight", (768, 2304))) == (None, "tp")
    assert tuple(plan("transformer.h.0.attn.c_proj.weight", (768, 768))) == ("tp", None)
    assert tuple(plan("transformer.wte.weight", (50257, 768))) == ("tp", None)
    assert tuple(plan("transformer.h.0.ln_1.weight", (768,))) == ()


def test_tp_plan_llama_specs():
    plan = tp_plan_llama()
    assert tuple(plan("model.layers.0.self_attn.q_proj.weight", (4096, 4096))) == ("tp", None)
    assert tuple(plan("model.layers.0.self_attn.o_proj.weight", (4096, 4096))) == (None, "tp")
    assert tuple(plan("model.layers.0.mlp.down_proj.weight", (4096, 11008))) == (None, "tp")


def test_fsdp_over_tp_2d():
    plan = fsdp_over(tp_plan_llama())
    spec = plan("model.layers.0.self_attn.q_proj.weight", (4096, 4096))
    assert tuple(spec) == ("tp", "fsdp")
    spec = plan("model.norm.weight", (4096,))
    assert tuple(spec) == ("fsdp",)


def test_gpt2_block_sharded_tp():
    from transformers.models.gpt2.modeling_gpt2 import GPT2Config, GPT2Block

    cfg = GPT2Config(n_layer=2, n_embd=256, n_head=4)
    mesh = make_mesh(MeshSpec(dp=2, tp=4))
    blk = di.deferred_init(GPT2Block, cfg)
    out = materialize_module_jax(blk, mesh=mesh, plan=tp_plan_gpt2())
    w = out["attn.c_attn.weight"]
    assert w.shape == (256, 768)
    # column-parallel over tp=4: each shard (256, 192), replicated over dp.
    shard_shapes = {s.data.shape for s in w.addressable_shards}
    assert shard_shapes == {(256, 192)}


def test_dtype_override_bf16():
    import jax.numpy as jnp

    m = di.deferred_init(nn.Linear, 32, 16)
    out = materialize_module_jax(m, dtype=torch.bfloat16)
    assert out["weight"].dtype == jnp.bfloat16


def test_rng_order_independence():
    # JAX path keys by op_nr: materializing params in any order gives the
    # same values (unlike the torch global-stream path).
    m = di.deferred_init(nn.Linear, 16, 8)
    both = materialize_module_jax(m, seed=3)
    w_only = materialize_tensor_jax(m.weight, seed=3)
    np.testing.assert_allclose(
        np.asarray(both["weight"]), np.asarray(w_only), rtol=1e-7
    )


def test_guard_failure_in_jax_path():
    ext = torch.ones(4)
    with di._deferred_init_context():
        t = torch.zeros(4)
        u = t + ext
    ext.add_(1)
    with pytest.raises(RuntimeError, match="mutated after recording"):
        materialize_tensor_jax(u)


def test_jax_cross_tape_module():
    m1 = di.deferred_init(nn.Linear, 4, 4)
    m2 = di.deferred_init(nn.Linear, 4, 4)
    seq = nn.Sequential(m1, m2)
    out = materialize_module_jax(seq)
    assert set(out) == {"0.weight", "0.bias", "1.weight", "1.bias"}
    assert not np.allclose(np.asarray(out["0.weight"]), np.asarray(out["1.weight"]))


class _DeepModel(nn.Module):
    """Repeated-block model: the grouped strategy's target shape (48-layer
    models record 48 structurally identical stacks per parameter kind)."""

    def __init__(self, depth=6, dim=32):
        super().__init__()
        self.emb = nn.Embedding(100, dim)
        self.blocks = nn.ModuleList(
            [nn.Linear(dim, dim) for _ in range(depth)]
        )
        self.norm = nn.LayerNorm(dim)


def test_grouped_matches_fused():
    m = di.deferred_init(_DeepModel)
    fused = materialize_module_jax(m, strategy="fused")
    grouped = materialize_module_jax(m, strategy="grouped")
    assert set(fused) == set(grouped)
    for k in fused:
        np.testing.assert_allclose(
            np.asarray(fused[k]), np.asarray(grouped[k]), rtol=1e-7
        )


def test_grouped_matches_fused_sharded():
    mesh = make_mesh(MeshSpec(fsdp=8))
    m = di.deferred_init(_DeepModel, depth=4, dim=64)
    fused = materialize_module_jax(
        m, mesh=mesh, plan=fsdp_plan(min_size=1), strategy="fused"
    )
    grouped = materialize_module_jax(
        m, mesh=mesh, plan=fsdp_plan(min_size=1), strategy="grouped"
    )
    for k in fused:
        assert fused[k].sharding == grouped[k].sharding, k
        np.testing.assert_allclose(
            np.asarray(fused[k]), np.asarray(grouped[k]), rtol=1e-7
        )


def test_grouped_handles_aliased_params_via_fused_fallback():
    # Params whose stacks share nodes must take the fused path inside the
    # grouped strategy (write-ordering through aliases).
    class M(nn.Module):
        pass

    with di._deferred_init_context():
        t = torch.zeros(4)
        u = t + 1
        t.add_(5)
        mod = M()
        mod.t = nn.Parameter(t)
        mod.u = nn.Parameter(u)
        mod.lin = nn.Linear(4, 4)  # groupable alongside
    out = materialize_module_jax(mod, strategy="grouped")
    np.testing.assert_allclose(np.asarray(out["t"]), np.full((4,), 5.0))
    np.testing.assert_allclose(np.asarray(out["u"]), np.ones(4))
    assert out["lin.weight"].shape == (4, 4)


def test_jax_order_independent_aliasing():
    class M(nn.Module):
        pass

    with di._deferred_init_context():
        t = torch.zeros(4)
        u = t + 1
        t.add_(5)
        mod = M()
        mod.t = nn.Parameter(t)
        mod.u = nn.Parameter(u)
    out = materialize_module_jax(mod)
    np.testing.assert_allclose(np.asarray(out["t"]), np.full((4,), 5.0))
    np.testing.assert_allclose(np.asarray(out["u"]), np.ones(4))


def test_rng_cross_tape_reproducibility():
    """Same architecture recorded in two different tapes materializes to
    identical values (streams key on tape-relative identities, never the
    process-global op counter) — and the second materialization reuses the
    first's compiled executable outright (exec cache)."""
    import torchdistx_tpu.materialize as M

    m1 = di.deferred_init(_DeepModel)
    a1 = materialize_module_jax(m1, seed=5)
    hits_before = M.exec_cache_hits
    m2 = di.deferred_init(_DeepModel)
    a2 = materialize_module_jax(m2, seed=5)
    assert M.exec_cache_hits == hits_before + 1
    assert set(a1) == set(a2)
    for k in a1:
        np.testing.assert_array_equal(np.asarray(a1[k]), np.asarray(a2[k]))
    # Distinct same-signature params still draw distinct streams.
    assert not np.array_equal(
        np.asarray(a1["blocks.0.weight"]), np.asarray(a1["blocks.1.weight"])
    )


def test_exec_cache_seed_sweep_and_dtype():
    import torchdistx_tpu.materialize as M

    m1 = di.deferred_init(nn.Linear, 16, 8)
    m2 = di.deferred_init(nn.Linear, 16, 8)
    m3 = di.deferred_init(nn.Linear, 16, 8)
    a1 = materialize_module_jax(m1, seed=1)
    hits_before = M.exec_cache_hits
    # The base key is a traced input: a seed sweep reuses one executable
    # while still drawing distinct values.
    a2 = materialize_module_jax(m2, seed=2)
    assert M.exec_cache_hits == hits_before + 1
    assert not np.array_equal(np.asarray(a1["weight"]), np.asarray(a2["weight"]))
    a3 = materialize_module_jax(m3, seed=1, dtype=torch.bfloat16)
    assert M.exec_cache_hits == hits_before + 1  # different dtype: no reuse
    assert str(a3["weight"].dtype) == "bfloat16"


def test_mono_fast_path_matches_per_job_path(monkeypatch):
    """The mono executable (whole materialization as one program — one
    executable load on a cached-cold run) must produce bitwise the
    same values as the per-job path and count as a cache-hit run."""
    import torchdistx_tpu.materialize as M

    monkeypatch.setenv("TDX_PROFILE_MATERIALIZE", "1")
    m1 = di.deferred_init(_DeepModel)
    materialize_module_jax(m1, seed=9)  # compiles jobs + seeds mono (mem)
    hits = M.exec_cache_hits
    m2 = di.deferred_init(_DeepModel)
    a2 = materialize_module_jax(m2, seed=9)  # mono mem-tier hit
    assert M.exec_cache_hits == hits + 1
    # Prove the mono executable actually served the second call.
    assert any(lbl == "mono" for lbl, _, _ in M.last_profile["jobs"]), (
        M.last_profile
    )
    monkeypatch.setenv("TDX_NO_MONO", "1")
    m3 = di.deferred_init(_DeepModel)
    a3 = materialize_module_jax(m3, seed=9)  # per-job path
    assert set(a2) == set(a3)
    for k in a2:
        np.testing.assert_array_equal(np.asarray(a2[k]), np.asarray(a3[k]))


def test_bigfill_classes_2d_plan_match_tensor_path(monkeypatch):
    """Large fills (> FILL_POOL_MAX) on a 2-D tp×fsdp mesh take the
    big-fill class path with mixed dim-0/dim-1 shardings; values must be
    bitwise-equal to the single-device tensor path and actually sharded."""
    from transformers import LlamaConfig, LlamaForCausalLM

    import torchdistx_tpu.materialize as M

    monkeypatch.setenv("TDX_PROFILE_MATERIALIZE", "1")
    config = LlamaConfig(
        # embed/lm_head (4096×512) and the mlp mats (512×2752, sharded on
        # dim 1 by the tp plan) are all > FILL_POOL_MAX → big-fill classes
        # with mixed dim-0/dim-1 specs; q_proj (512²) stays pooled.
        vocab_size=4096, hidden_size=512, intermediate_size=2752,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=8, max_position_embeddings=64,
    )
    model = di.deferred_init(LlamaForCausalLM, config)
    mesh = make_mesh(MeshSpec(fsdp=2, tp=4))
    arrays = materialize_module_jax(
        model, mesh=mesh, plan=combine_plans(tp_plan_llama(), fsdp_plan())
    )
    fakes = dict(model.named_parameters())
    # The class path must have actually served this materialization.
    assert any(
        lbl == "bigfillcls" for lbl, _, _ in M.last_profile["jobs"]
    ), M.last_profile
    embed = arrays["model.embed_tokens.weight"]  # 4096×512 = 2.1M > pool max
    assert not embed.sharding.is_fully_replicated
    for name in (
        "model.embed_tokens.weight",
        "model.layers.0.self_attn.q_proj.weight",
        "model.layers.1.mlp.down_proj.weight",
    ):
        got = np.asarray(arrays[name])
        want = np.asarray(materialize_tensor_jax(fakes[name]))
        np.testing.assert_array_equal(got, want, err_msg=name)


def test_tensor_path_cross_tape_streams_distinct():
    """A call stack spanning two tapes draws distinct streams per tape —
    same-relative-offset RNG ops must not produce identical values."""
    t1 = di.deferred_init(lambda: torch.empty(8).uniform_())

    def second():
        return torch.empty(8).uniform_().add_(t1 * 0)

    t2 = di.deferred_init(second)
    v1 = np.asarray(materialize_tensor_jax(t1, seed=0))
    v2 = np.asarray(materialize_tensor_jax(t2, seed=0))
    assert not np.allclose(v1, v2)


def test_pow_lowering_values():
    """pow.Scalar is the one lowering whose FIRST aten arg is the scalar
    (scalar-base ** tensor-exponent, HF Llama's RoPE inv_freq) — lock the
    argument order against eager torch."""
    with di._deferred_init_context():
        exp = torch.arange(0, 8, 2, dtype=torch.float32) / 8
        t = 2.0 ** -exp                      # aten.pow.Scalar
        u = exp ** 2.0                       # aten.pow.Tensor_Scalar
        w = exp ** torch.full((4,), 3.0)     # aten.pow.Tensor_Tensor
    exp_e = np.arange(0, 8, 2, dtype=np.float32) / 8
    np.testing.assert_allclose(
        np.asarray(materialize_tensor_jax(t)), 2.0 ** -exp_e, rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(materialize_tensor_jax(u)), exp_e**2.0, rtol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(materialize_tensor_jax(w)), exp_e**3.0, rtol=1e-6
    )


# -- multi-mutation scatter (VERDICT r2 weak #5) ----------------------------

_TWOMUT_LIB = None


def _twomut_op():
    """A custom op mutating TWO positional args, each aliased by its own
    return — the shape that exposed the old outs[0]-everywhere scatter."""
    global _TWOMUT_LIB
    if _TWOMUT_LIB is None:
        lib = torch.library.Library("tdxtest", "DEF")  # noqa: TOR901
        lib.define(
            "twomut(Tensor(a!) x, Tensor(b!) y) -> (Tensor(a!), Tensor(b!))"
        )

        def impl(x, y):
            x.add_(1.0)
            y.mul_(2.0)
            return x, y

        lib.impl("twomut", impl, "CompositeExplicitAutograd")
        lib.impl("twomut", impl, "Meta")
        from torchdistx_tpu.ops import LOWERINGS

        LOWERINGS["tdxtest.twomut.default"] = (
            lambda ctx, x, y: (x + 1.0, y * 2.0)
        )
        _TWOMUT_LIB = lib
    return torch.ops.tdxtest.twomut


def test_two_mutated_args_each_get_own_result():
    op = _twomut_op()
    with di._deferred_init_context():
        x = torch.zeros(4)
        y = torch.ones(4)
        op(x, y)
    np.testing.assert_allclose(np.asarray(materialize_tensor_jax(x)), 1.0)
    # Old scatter wrote outs[0] (= x+1 = 1.0) here instead of y*2.
    np.testing.assert_allclose(np.asarray(materialize_tensor_jax(y)), 2.0)


def test_out_variant_kwarg_only_mutation():
    """aminmax.out mutates two kwarg-ONLY buffers; each must receive its own
    schema-aliased return through the replay scatter."""
    with di._deferred_init_context():
        src = torch.arange(6.0).view(2, 3)
        mn = torch.zeros(2)
        mx = torch.zeros(2)
        torch.aminmax(src, dim=1, out=(mn, mx))
        mn.add_(0.0)  # force post-mutation read through the buffers
        mx.add_(0.0)
    np.testing.assert_allclose(
        np.asarray(materialize_tensor_jax(mn)), [0.0, 3.0]
    )
    np.testing.assert_allclose(
        np.asarray(materialize_tensor_jax(mx)), [2.0, 5.0]
    )


def test_exec_cache_is_lru():
    """A hit refreshes recency, so hot entries survive eviction (ADVICE r2)."""
    import torchdistx_tpu.materialize as M

    saved = dict(M._EXEC_CACHE)
    M._EXEC_CACHE.clear()
    try:
        M._exec_cache_put("hot", "H")
        for i in range(M._EXEC_CACHE_MAX - 1):
            M._exec_cache_put(f"cold{i}", i)
        assert M._exec_cache_get("hot") == "H"  # refresh: back of the queue
        M._exec_cache_put("new", "N")           # evicts cold0, not hot
        assert "hot" in M._EXEC_CACHE
        assert "cold0" not in M._EXEC_CACHE
    finally:
        M._EXEC_CACHE.clear()
        M._EXEC_CACHE.update(saved)
