"""Attention ops: flash (Pallas, interpreted on CPU) and ring vs reference.

Test rig per SURVEY.md §4: single host, virtual 8-device CPU mesh.
"""

import base64
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from torchdistx_tpu.ops.attention import attention, mha_reference
from torchdistx_tpu.ops.pallas.flash_attention import flash_attention
from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh
from torchdistx_tpu.parallel.ring_attention import ring_attention


def _qkv(b=2, s=64, hq=4, hkv=2, d=16, dtype=jnp.float32):
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, s, hq, d), dtype=dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d), dtype=dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d), dtype=dtype)
    return q, k, v


def _grads(fn, q, k, v, w):
    return jax.grad(
        lambda q, k, v: (fn(q, k, v) * w).sum(), argnums=(0, 1, 2)
    )(q, k, v)


def _flash_bwd_built(fn):
    """``(fn(), {kernel: traces})``: which flash backward ``fn`` built, as
    ``attention.flash_bwd{kernel=...}`` counted it."""
    from torchdistx_tpu import telemetry

    c0 = telemetry.counters()
    out = fn()
    c1 = telemetry.counters()
    prefix = "attention.flash_bwd{kernel="
    return out, {
        key[len(prefix):-1]: c1[key] - c0.get(key, 0)
        for key in c1
        if key.startswith(prefix) and c1[key] != c0.get(key, 0)
    }


class TestFlashTwoWidths:
    """q and k of one head width, v and the output of another (latent
    attention: 192 against 128), through all four kernels."""

    @pytest.mark.parametrize("backward", ["fused", "streamed", "fused_stream"])
    @pytest.mark.parametrize("d_qk,d_v", [(192, 128), (48, 32)])
    def test_forward_and_gradients_match_reference(
        self, d_qk, d_v, backward, monkeypatch
    ):
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        if backward != "fused":  # several q and kv blocks
            monkeypatch.setattr(fa, "_BWD_BLOCK_Q", 128)
            monkeypatch.setattr(fa, "_BWD_BLOCK_KV", 128)
            monkeypatch.setattr(fa, "_FWD_BLOCK_KV", 128)
        if backward == "streamed":  # no room for dq in VMEM: three kernels
            monkeypatch.setattr(fa, "_FUSED_BWD_DQ_VMEM", 0)
        b, s, hq, hkv = 1, 384, 4, 2
        ks = jax.random.split(jax.random.PRNGKey(d_qk), 4)
        q = jax.random.normal(ks[0], (b, s, hq, d_qk))
        k = jax.random.normal(ks[1], (b, s, hkv, d_qk))
        v = jax.random.normal(ks[2], (b, s, hkv, d_v))
        w = jax.random.normal(ks[3], (b, s, hq, d_v))
        flash = functools.partial(flash_attention, causal=True, interpret=True)
        n_kernels = str(jax.make_jaxpr(
            lambda *a: _grads(flash, *a, w)
        )(q, k, v)).count("pallas_call")
        assert n_kernels == (3 if backward == "streamed" else 2)
        out = flash(q, k, v)
        ref = mha_reference(q, k, v, causal=True)
        assert out.shape == ref.shape == (b, s, hq, d_v)
        assert jnp.allclose(out, ref, atol=2e-5)
        for name, g, r in zip(
            "qkv", _grads(flash, q, k, v, w),
            _grads(functools.partial(mha_reference, causal=True), q, k, v, w),
        ):
            assert g.shape == r.shape, name
            assert jnp.allclose(g, r, atol=1e-4), name

    @pytest.mark.parametrize("causal", [True, False])
    @pytest.mark.parametrize("seq", [384, 400])  # 3 and 4 blocks, 400 padded
    @pytest.mark.parametrize("groups", [1, 2])
    @pytest.mark.parametrize("d_qk,d_v", [(192, 128), (48, 32), (32, 32)])
    def test_one_backward_kernel_over_several_kv_blocks(
        self, d_qk, d_v, groups, seq, causal, monkeypatch
    ):
        """dq, dk and dv of the one-kernel backward past one kv block
        (the sequence's dq resident across the kv blocks) against
        ``mha_reference``, and against the streamed pair on the same
        inputs: the same p, ds and f32 sums in the same order."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        monkeypatch.setattr(fa, "_BWD_BLOCK_Q", 128)
        monkeypatch.setattr(fa, "_BWD_BLOCK_KV", 128)
        ks = jax.random.split(jax.random.PRNGKey(seq + d_qk), 4)
        q = jax.random.normal(ks[0], (1, seq, groups, d_qk))
        k = jax.random.normal(ks[1], (1, seq, 1, d_qk))
        v = jax.random.normal(ks[2], (1, seq, 1, d_v))
        w = jax.random.normal(ks[3], (1, seq, groups, d_v))
        flash = functools.partial(
            flash_attention, causal=causal, interpret=True
        )

        def built(budget):
            monkeypatch.setattr(fa, "_FUSED_BWD_DQ_VMEM", budget)
            return _flash_bwd_built(lambda: _grads(flash, q, k, v, w))

        fused, kernel = built(fa._FUSED_BWD_DQ_VMEM)
        assert kernel == {"fused": 1}
        pair, kernel = built(0)
        assert kernel == {"pair": 1}
        ref = _grads(
            functools.partial(mha_reference, causal=causal), q, k, v, w
        )
        for name, g, p, r in zip("qkv", fused, pair, ref):
            assert g.shape == r.shape, name
            assert jnp.allclose(g, r, atol=1e-4), name
            assert jnp.allclose(g, p, atol=1e-4), name

    def test_the_shapes_alone_choose_the_backward(self):
        """One kv block builds ``fused_nk1``; several build ``fused`` while
        the sequence's f32 dq, ``groups * s_pad * lanes(d_qk) * 4`` bytes,
        is within ``_FUSED_BWD_DQ_VMEM``, and the streamed ``pair`` past
        it: the same call, nothing set, read from the counter."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        def built(seq, hq, hkv, d):
            q = jax.ShapeDtypeStruct((1, seq, hq, d), jnp.bfloat16)
            kv = jax.ShapeDtypeStruct((1, seq, hkv, d), jnp.bfloat16)
            grad = jax.grad(
                lambda q, k, v: flash_attention(q, k, v, interpret=True)
                .astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )
            return _flash_bwd_built(lambda: jax.eval_shape(grad, q, kv, kv))[1]

        under = fa._FUSED_BWD_DQ_VMEM // (256 * 4)  # positions of 192-wide heads
        assert under >= 8192
        assert built(1024, 2, 2, 64) == {"fused_nk1": 1}
        assert built(8192, 2, 2, 192) == {"fused": 1}
        assert built(under, 1, 1, 192) == {"fused": 1}
        assert built(2 * under, 1, 1, 192) == {"pair": 1}
        # The same positions at 128 lanes fit; a GQA group of four does not.
        assert built(2 * under, 1, 1, 128) == {"fused": 1}
        assert built(2 * under, 4, 1, 128) == {"pair": 1}

    @pytest.mark.parametrize(
        "seq,heads,d,dq_vmem,pinned",
        [
            (1024, 4, 64, None, "b877060f52d6c20b4175a1ea56c04f39da24e15ed5bbb0ecb8edde73772688d4"),
            (4096, 2, 64, None, "7f565f94ee1e9f3dcd84f4944d5e25b4d848bdf8623d5e7df529f57ec27d9597"),
            (4096, 2, 128, None, "fe8906d8bdb3665a97cc3b1492797b81489df29dffe8dbad5ebb47606b13af70"),
            (4096, 2, 64, 0, "d3af703b63c17d89f0e2bc8b66e4c3c6836a40b0734f3bc5d3631708b89d15ce"),
            (4096, 2, 128, 0, "d3440a3b7169f59a843fd57b615c2d80fcba4ff9b5ed415a2a14bb84bc0f5e3e"),
        ],
    )
    def test_equal_widths_lower_to_the_program_of_pr_26(
        self, seq, heads, d, dq_vmem, pinned, monkeypatch
    ):
        """With ``d_qk == d_v`` the kernels are the ones that took a single
        width: the gradient's StableHLO lowered for the TPU, each Mosaic
        body printed without its source locations, hashes to what commit
        bb7fb95 (PR 26) gives: the single-block backward at 1024, and at
        4096 the streamed pair, which a shape takes since PR 28 only with
        no room for dq in VMEM (``dq_vmem`` 0).  The two hashes at 4096 as
        shapes alone choose it are PR 28's, re-pinned when the backward
        there became one kernel.  A PR that changes the kernels on purpose
        re-pins."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        if dq_vmem is not None:
            monkeypatch.setattr(fa, "_FUSED_BWD_DQ_VMEM", dq_vmem)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        x = jax.ShapeDtypeStruct((1, seq, heads, d), jnp.bfloat16)
        grad = jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
        text = jax.jit(grad).trace(x, x, x).lower(
            lowering_platforms=("tpu",)
        ).as_text()
        assert hashlib.sha256(_without_locations(text).encode()).hexdigest() == pinned


def _without_locations(stablehlo: str) -> str:
    """Each Mosaic kernel body (MLIR bytecode in base64, which carries file
    names and line numbers) replaced by the hash of its text without them."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def body(m):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True
        with ctx:
            module = ir.Module.parse(base64.b64decode(m.group(1)))
            asm = module.operation.get_asm(enable_debug_info=False)
        return '"body": "' + hashlib.sha256(asm.encode()).hexdigest() + '"'

    out, n = re.subn(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', body, stablehlo)
    assert n, "no Mosaic kernel in the lowered text"
    return out


class TestFlashAttention:
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_mha_no_gqa(self):
        q, k, v = _qkv(hq=4, hkv=4)
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_grads_match_reference(self):
        q, k, v = _qkv(s=32)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        g_ref = jax.grad(
            loss(lambda q, k, v: mha_reference(q, k, v, causal=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fa = jax.grad(
            loss(
                lambda q, k, v: flash_attention(
                    q, k, v, causal=True, interpret=True
                )
            ),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ref, g_fa):
            assert jnp.allclose(a, b, atol=1e-4)

    def test_long_seq_multiple_q_blocks(self):
        # seq > block size → several q-block grid steps.
        q, k, v = _qkv(s=512, d=8)
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_grads_multi_block_gqa(self):
        # Backward kernels across several q/kv blocks (s=512 → multiple
        # grid steps on the streamed axes) with GQA group reduction —
        # exercises the causal diagonal-clamped index maps end to end.
        key = jax.random.PRNGKey(3)
        b, s, hq, hkv, d = 2, 512, 4, 2, 16
        q = jax.random.normal(key, (b, s, hq, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))

        def loss_fa(q, k, v):
            return (flash_attention(q, k, v, causal=True, interpret=True) ** 2).sum()

        def loss_ref(q, k, v):
            return (mha_reference(q, k, v, causal=True) ** 2).sum()

        g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ref, g_fa):
            assert jnp.allclose(a, b_, atol=5e-4)

    def test_fused_bwd_matches_two_kernel_path(self):
        """The fused nk==1 backward (training regime) and the streamed
        two-kernel backward (long-context regime) must compute the same
        gradients — only f32 accumulation order differs."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        key = jax.random.PRNGKey(7)
        b, s, hq, hkv, d = 2, 256, 4, 2, 32
        q = jax.random.normal(key, (b, s, hq, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))

        def loss(q, k, v):
            return (
                flash_attention(q, k, v, causal=True, interpret=True) ** 2
            ).sum()

        # Spy on the fused kernel entry so the test cannot pass vacuously
        # if the dispatch condition ever drifts.
        fused_calls = []
        orig_fused = fa._fa_backward_fused_nk1

        def spy(*a, **kw):
            fused_calls.append(1)
            return orig_fused(*a, **kw)

        old = fa._BWD_BLOCK_Q, fa._BWD_BLOCK_KV, fa._FUSED_BWD_DQ_VMEM
        fa._fa_backward_fused_nk1 = spy
        try:
            # Defaults: bkv == s_pad, fused single-kernel path.
            g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            assert fused_calls, "defaults no longer take the fused path"
            n_fused = len(fused_calls)
            # Force two kv blocks and leave dq no room in VMEM: the
            # streamed dq + dkv kernel pair.
            fa._BWD_BLOCK_Q, fa._BWD_BLOCK_KV = 128, 128
            fa._FUSED_BWD_DQ_VMEM = 0
            jax.clear_caches()
            g_two = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            assert len(fused_calls) == n_fused, (
                "128-block override still took the fused path"
            )
        finally:
            fa._fa_backward_fused_nk1 = orig_fused
            fa._BWD_BLOCK_Q, fa._BWD_BLOCK_KV, fa._FUSED_BWD_DQ_VMEM = old
            jax.clear_caches()
        for a, b_ in zip(g_fused, g_two):
            assert jnp.allclose(a, b_, atol=5e-5)

    def test_fused_bwd_2048_gradients(self):
        """S=2048 takes the fused backward with bkv = s_pad (above the
        1024 default block — the _FUSED_BWD_MAX_KV extension); gradients
        must match the dense reference."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        key = jax.random.PRNGKey(9)
        b, s, h, d = 1, 2048, 1, 32
        q = jax.random.normal(key, (b, s, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))

        fused_calls = []
        orig = fa._fa_backward_fused_nk1

        def spy(*a, **kw):
            fused_calls.append(1)
            return orig(*a, **kw)

        fa._fa_backward_fused_nk1 = spy
        try:
            def loss_fa(q, k, v):
                return (
                    flash_attention(q, k, v, causal=True, interpret=True)
                    ** 2
                ).sum()

            def loss_ref(q, k, v):
                return (mha_reference(q, k, v, causal=True) ** 2).sum()

            g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
            assert fused_calls, "S=2048 did not take the fused path"
        finally:
            fa._fa_backward_fused_nk1 = orig
        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(g_ref, g_fa):
            assert jnp.allclose(a, b_, atol=5e-4)

    def test_fused_bwd_vmem_guard_falls_back_to_streamed(self):
        """When even bq=128 cannot fit the (bq, s_pad) f32 p/ds working
        set under the VMEM cap, the fused backward must hand off to the
        streamed two-kernel path instead of overflowing — with identical
        gradients."""
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        key = jax.random.PRNGKey(11)
        b, s, h, d = 1, 256, 2, 32
        q = jax.random.normal(key, (b, s, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))

        def loss(q, k, v):
            return (
                flash_attention(q, k, v, causal=True, interpret=True) ** 2
            ).sum()

        g_fused = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

        streamed_calls = []
        orig_streamed = fa._fa_backward_streamed

        def spy(*a, **kw):
            streamed_calls.append(kw)
            return orig_streamed(*a, **kw)

        old_cap = fa._FUSED_BWD_VMEM_CAP
        fa._fa_backward_streamed = spy
        try:
            # Cap below the bq=128 working set (128·256·4 bytes): the
            # fused path cannot whittle its way under and must fall back.
            fa._FUSED_BWD_VMEM_CAP = 128 * s * 4 - 1
            jax.clear_caches()
            g_streamed = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
            assert streamed_calls, "VMEM guard did not fall back"
            # The handoff must NOT pin the streamed path to the whittled
            # bq=128 — against _block_for-sized kv blocks its own default
            # q block fits the cap and runs far fewer grid iterations.
            assert "bq" not in streamed_calls[0]
            assert streamed_calls[0]["bkv"] == fa._block_for(s)
        finally:
            fa._fa_backward_streamed = orig_streamed
            fa._FUSED_BWD_VMEM_CAP = old_cap
            jax.clear_caches()
        for a, b_ in zip(g_fused, g_streamed):
            assert jnp.allclose(a, b_, atol=5e-5)

    def test_long_context_kv_streaming(self):
        # The long-context regime the kernel exists for: 8 q-blocks ×
        # 8 kv-blocks streamed through the VMEM scratch accumulators.
        # (16k/32k fwd+bwd are exercised on real TPU hardware via the bench
        # and graft entry; the interpreter at that size is impractical.)
        key = jax.random.PRNGKey(0)
        b, s, h, d = 1, 2048, 2, 64
        q = jax.random.normal(key, (b, s, h, d))
        k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, h, d))
        v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, interpret=True)
        assert jnp.allclose(ref, out, atol=1e-5)


class TestRingAttention:
    @pytest.fixture(scope="class")
    def mesh(self):
        return make_mesh(MeshSpec(dp=2, sp=4))

    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_reference(self, mesh, causal):
        q, k, v = _qkv()
        ref = mha_reference(q, k, v, causal=causal)
        out = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh=mesh, axis="sp", causal=causal
            )
        )(q, k, v)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_zigzag_matches_reference(self, mesh):
        q, k, v = _qkv()  # s=64 = 2·sp·8
        ref = mha_reference(q, k, v, causal=True)
        out = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh=mesh, axis="sp", causal=True,
                schedule="zigzag",
            )
        )(q, k, v)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_zigzag_grads_match_reference(self, mesh):
        q, k, v = _qkv()
        g_ref = jax.grad(
            lambda q, k, v: (mha_reference(q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_z = jax.jit(jax.grad(
            lambda q, k, v: (
                ring_attention(
                    q, k, v, mesh=mesh, axis="sp", causal=True,
                    schedule="zigzag",
                ) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        ))(q, k, v)
        for a, b in zip(g_ref, g_z):
            assert jnp.allclose(a, b, atol=1e-4)

    def test_zigzag_validation(self, mesh):
        q, k, v = _qkv()
        with pytest.raises(ValueError, match="causal-only"):
            ring_attention(
                q, k, v, mesh=mesh, axis="sp", causal=False,
                schedule="zigzag",
            )
        q2, k2, v2 = _qkv(s=36)  # not divisible by 2·sp=8
        with pytest.raises(ValueError, match="divisible"):
            ring_attention(
                q2, k2, v2, mesh=mesh, axis="sp", causal=True,
                schedule="zigzag",
            )

    def test_causal_skips_future_blocks(self, mesh):
        """Future K/V ring blocks take a lax.cond identity branch; the
        compiled module retains a real HLO conditional (skipped, not
        select-executed) in forward and backward."""
        q, k, v = _qkv()
        fwd = jax.jit(
            lambda q, k, v: ring_attention(
                q, k, v, mesh=mesh, axis="sp", causal=True
            )
        )
        assert "conditional" in fwd.lower(q, k, v).compile().as_text()
        bwd = jax.jit(jax.grad(
            lambda q, k, v: (
                ring_attention(q, k, v, mesh=mesh, axis="sp", causal=True)
                ** 2
            ).sum(),
            argnums=(0,),
        ))
        assert "conditional" in bwd.lower(q, k, v).compile().as_text()

    def test_grads_match_reference(self, mesh):
        q, k, v = _qkv(s=32)
        g_ref = jax.grad(
            lambda q, k, v: (mha_reference(q, k, v, causal=True) ** 2).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_ring = jax.jit(
            jax.grad(
                lambda q, k, v: (
                    ring_attention(q, k, v, mesh=mesh, axis="sp") ** 2
                ).sum(),
                argnums=(0, 1, 2),
            )
        )(q, k, v)
        for a, b in zip(g_ref, g_ring):
            assert jnp.allclose(a, b, atol=1e-4)

    def test_sp_only_mesh(self):
        mesh = make_mesh(MeshSpec(sp=8))
        q, k, v = _qkv(s=64)
        ref = mha_reference(q, k, v, causal=True)
        out = jax.jit(
            lambda q, k, v: ring_attention(q, k, v, mesh=mesh, axis="sp")
        )(q, k, v)
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_missing_axis_raises(self, mesh):
        q, k, v = _qkv(s=8)
        with pytest.raises(ValueError, match="no axis"):
            ring_attention(q, k, v, mesh=mesh, axis="nope")


class TestDispatcher:
    def test_auto_cpu_is_jnp(self):
        q, k, v = _qkv(s=16)
        out = attention(q, k, v, causal=True)
        assert jnp.allclose(out, mha_reference(q, k, v, causal=True), atol=1e-5)

    def test_ring_requires_mesh(self):
        q, k, v = _qkv(s=16)
        with pytest.raises(ValueError, match="mesh"):
            attention(q, k, v, impl="ring")


class TestShardedFlash:
    """shard_map-wrapped Pallas kernel under the mesh (VERDICT r2 item 1)."""

    @pytest.mark.parametrize("spec", [
        MeshSpec(dp=2, fsdp=2, tp=2),
        MeshSpec(fsdp=8),
        MeshSpec(tp=2, dp=4),
    ])
    def test_values_match_reference(self, spec):
        from torchdistx_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        mesh = make_mesh(spec)
        q, k, v = _qkv(b=8, s=64, hq=4, hkv=2)
        ref = mha_reference(q, k, v, causal=True)
        out = flash_attention_sharded(
            q, k, v, causal=True, mesh=mesh, interpret=True
        )
        assert jnp.allclose(ref, out, atol=1e-5)

    def test_grads_match_reference(self):
        from torchdistx_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        mesh = make_mesh(MeshSpec(dp=2, tp=2, fsdp=2))
        q, k, v = _qkv(b=4, s=32, hq=4, hkv=4)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v) ** 2).sum()

        g_ref = jax.grad(
            loss(lambda q, k, v: mha_reference(q, k, v, causal=True)),
            argnums=(0, 1, 2),
        )(q, k, v)
        g_fa = jax.grad(
            loss(lambda q, k, v: flash_attention_sharded(
                q, k, v, causal=True, mesh=mesh, interpret=True
            )),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(g_ref, g_fa):
            assert jnp.allclose(a, b, atol=1e-4)

    def test_inside_jit_with_sharded_inputs(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        from torchdistx_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        mesh = make_mesh(MeshSpec(dp=2, tp=2, fsdp=2))
        q, k, v = _qkv(b=4, s=32, hq=8, hkv=8)
        sh = NamedSharding(mesh, P(("dp", "fsdp"), None, "tp", None))
        q, k, v = (jax.device_put(t, sh) for t in (q, k, v))
        out = jax.jit(
            lambda q, k, v: flash_attention_sharded(
                q, k, v, causal=True, mesh=mesh, interpret=True
            )
        )(q, k, v)
        ref = mha_reference(q, k, v, causal=True)
        assert jnp.allclose(ref, out, atol=1e-5)
        assert out.sharding.is_equivalent_to(sh, 4)

    def test_shardable_predicate(self):
        from torchdistx_tpu.ops.pallas.flash_attention import shardable

        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        assert shardable(mesh, (8, 64, 4, 16), (8, 64, 2, 16))
        # batch 3 not divisible by dp*fsdp=4
        assert not shardable(mesh, (3, 64, 4, 16), (3, 64, 2, 16))
        # kv heads 1 not divisible by tp=2
        assert not shardable(mesh, (8, 64, 4, 16), (8, 64, 1, 16))

    def test_indivisible_raises(self):
        from torchdistx_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        q, k, v = _qkv(b=3, s=32)
        with pytest.raises(ValueError, match="not.*divisible|divisible"):
            flash_attention_sharded(q, k, v, mesh=mesh, interpret=True)


class TestAutoSelection:
    def test_auto_under_mesh_on_tpu_picks_pallas(self, monkeypatch):
        from torchdistx_tpu.ops import attention as A

        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
        assert A._select_impl(
            "auto", mesh, None, (8, 64, 4, 16), (8, 64, 2, 16)
        ) == "pallas"
        # indivisible shapes fall back to jnp
        assert A._select_impl(
            "auto", mesh, None, (3, 64, 4, 16), (3, 64, 2, 16)
        ) == "jnp"
        # seq parallelism still wins
        assert A._select_impl(
            "auto", mesh, "sp", (8, 64, 4, 16), (8, 64, 2, 16)
        ) == "ring"
        assert A._select_impl(
            "auto", None, None, (8, 64, 4, 16), (8, 64, 2, 16)
        ) == "pallas"

    def test_on_tpu_propagates_backend_error(self, monkeypatch):
        """A backend that fails to initialize is an error, never "not on
        TPU": impl="auto" must not quietly train on the jnp path."""
        from torchdistx_tpu.ops import attention as A

        def broken():
            raise RuntimeError("Unable to initialize backend 'tpu'")

        A._on_tpu.cache_clear()
        monkeypatch.setattr(jax, "devices", broken)
        try:
            with pytest.raises(RuntimeError, match="initialize backend"):
                A._on_tpu()
            with pytest.raises(RuntimeError, match="initialize backend"):
                A._select_impl(
                    "auto", None, None, (8, 64, 4, 16), (8, 64, 2, 16)
                )
        finally:
            monkeypatch.undo()
            A._on_tpu.cache_clear()
        assert A._on_tpu() is False  # the error was not cached as an answer

    def test_resolved_choice_is_counted(self):
        """What ``attention`` resolved to, and whether the flash kernel ran
        interpreted, is observable per trace (chip_smoke.py asserts on it)."""
        from torchdistx_tpu import telemetry

        def delta(fn):
            c0 = telemetry.counters()
            fn()
            c1 = telemetry.counters()
            return {
                k: c1[k] - c0.get(k, 0)
                for k in c1
                if k.startswith("attention.") and c1[k] != c0.get(k, 0)
            }

        q, k, v = _qkv(b=1, s=16)
        # CPU backend: "auto" resolves to jnp — and says so.
        assert delta(lambda: attention(q, k, v, impl="auto")) == {
            "attention.dispatch{impl=jnp}": 1
        }
        # An explicit kernel request off-TPU runs the interpreter — and
        # says so.
        assert delta(lambda: attention(q, k, v, impl="pallas")) == {
            "attention.dispatch{impl=pallas}": 1,
            "attention.flash{interpret=true}": 1,
        }

    @pytest.mark.parametrize(
        "s,n_calls",
        [
            (1024, 2),  # flash_fwd + flash_bwd_fused, one kv block
            (4096, 2),  # flash_fwd + flash_bwd_fused, four (3 until PR 28)
            (65536, 3),  # dq past the VMEM budget: the streamed pair
        ],
    )
    def test_kernels_lower_through_mosaic_for_tpu(self, s, n_calls):
        """All five kernels lower for the TPU platform with the installed
        JAX (Mosaic's Python-side lowering needs no chip): forward + a
        one-kernel backward while dq fits ``_FUSED_BWD_DQ_VMEM``, forward +
        the streamed dq and dk/dv pair past it."""

        def loss(q, k, v):
            out = flash_attention(q, k, v, causal=True, interpret=False)
            return out.astype(jnp.float32).sum()

        x = jax.ShapeDtypeStruct((1, s, 2, 64), jnp.bfloat16)
        lowered = (
            jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            .trace(x, x, x)
            .lower(lowering_platforms=("tpu",))
        )
        assert lowered.as_text().count("tpu_custom_call") == n_calls

    def test_pp_forward_pins_jnp(self):
        from torchdistx_tpu.models import llama

        cfg = llama.llama_test()
        mesh = make_mesh(MeshSpec(pp=2, dp=4))
        params = llama.init_params(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size
        )
        with pytest.raises(ValueError, match="pipeline stage"):
            llama.forward(
                params, tokens, cfg, mesh=mesh, pp_axis="pp",
                n_microbatches=2, attn_impl="pallas",
            )

    def test_auto_under_unknown_axis_names_is_jnp(self, monkeypatch):
        """A mesh with custom axis names ("data"/"model") must fall back to
        jnp — the wrapper only understands dp/fsdp/tp (review r3)."""
        from torchdistx_tpu.ops import attention as A

        monkeypatch.setattr(A, "_on_tpu", lambda: True)
        mesh = make_mesh(axis_names=("data", "model"), shape=(4, 2))
        assert A._select_impl(
            "auto", mesh, None, (8, 64, 4, 16), (8, 64, 2, 16)
        ) == "jnp"

    def test_slowmo_refuses_explicit_pallas(self):
        import optax
        from torchdistx_tpu.models import llama
        from torchdistx_tpu.parallel import train_step as ts
        from torchdistx_tpu.parallel.slowmo import SlowMomentumOptimizer

        cfg = llama.llama_test()
        mesh = make_mesh(MeshSpec(dp=2, fsdp=4))
        opt = SlowMomentumOptimizer(optax.sgd(0.1), base_lr=0.1, slowmo_freq=2)
        with pytest.raises(ValueError, match="SlowMo"):
            ts.make_slowmo_train_step(cfg, mesh, opt, attn_impl="pallas")


def test_noncausal_padded_grads_finite():
    """Non-causal + padded seq + very negative logits: padded kv cols'
    p = exp(-lse) must not overflow into NaN dq (review r3)."""
    b, s, h, d = 1, 100, 2, 16
    key = jax.random.PRNGKey(0)
    q = 50.0 * jax.random.normal(key, (b, s, h, d))
    k = -50.0 * q[:, :, :, :]  # strongly negative logits
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, h, d))
    g = jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, causal=False, interpret=True
        ).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for arr in g:
        assert bool(jnp.isfinite(arr).all())


class TestFlashWindow:
    """``window=W``: key ``j`` is visible to query ``t`` iff ``0 <= t - j <
    W``.  The banded kernels (``flash_win_*``) against the masked ``jnp``
    attention, the band's extent, and the plain kernels left as they were."""

    BACKWARDS = {
        # name: (q block, kv block, room for dq in VMEM) -> the counter
        "nk1": (None, None, None, "win_fused_nk1"),
        "fused": (64, 64, None, "win_fused"),
        "pair": (64, 64, 0, "win_pair"),
        "fused_tall": (128, 64, None, "win_fused"),
        "pair_wide": (64, 128, 0, "win_pair"),
    }

    def _blocks(self, monkeypatch, backward):
        from torchdistx_tpu.ops.pallas import flash_attention as fa

        bq, bkv, dq_vmem, counted = self.BACKWARDS[backward]
        for name in ("_FWD_BLOCK_Q", "_BWD_BLOCK_Q"):
            monkeypatch.setattr(fa, name, bq)
        for name in ("_FWD_BLOCK_KV", "_BWD_BLOCK_KV"):
            monkeypatch.setattr(fa, name, bkv)
        if dq_vmem is not None:
            monkeypatch.setattr(fa, "_FUSED_BWD_DQ_VMEM", dq_vmem)
        return counted

    # a multiple of the 64-blocks, not a multiple, smaller than a block
    @pytest.mark.parametrize("window", [128, 80, 20])
    @pytest.mark.parametrize("backward", list(BACKWARDS))
    @pytest.mark.parametrize("groups,seq", [(1, 200), (8, 256)])  # 200: padded
    def test_window_kernels_match_the_mask(
        self, window, backward, groups, seq, monkeypatch
    ):
        """Forward, dq, dk and dv of every backward kernel, over several q
        and kv blocks (``nk1``: the one kv block), against the explicit
        ``(T, T)`` mask."""
        counted = self._blocks(monkeypatch, backward)
        ks = jax.random.split(jax.random.PRNGKey(seq + window), 4)
        q = jax.random.normal(ks[0], (1, seq, groups, 16))
        k = jax.random.normal(ks[1], (1, seq, 1, 16))
        v = jax.random.normal(ks[2], (1, seq, 1, 16))
        w = jax.random.normal(ks[3], (1, seq, groups, 16))
        flash = functools.partial(flash_attention, interpret=True, window=window)
        masked = functools.partial(mha_reference, window=window)
        assert jnp.allclose(flash(q, k, v), masked(q, k, v), atol=2e-5)
        got, built = _flash_bwd_built(lambda: _grads(flash, q, k, v, w))
        assert built == {counted: 1}
        for name, g, r in zip("qkv", got, _grads(masked, q, k, v, w)):
            assert jnp.allclose(g, r, atol=1e-4), name
        # ... and the mask is not the plain triangle's
        assert not jnp.allclose(masked(q, k, v), mha_reference(q, k, v), atol=1e-3)

    @pytest.mark.parametrize("window", [80, None])  # banded, full
    @pytest.mark.parametrize("backward", ["fused", "pair"])
    def test_a_group_of_seven(self, window, backward, monkeypatch):
        """14 query heads on 2 key/value heads, a group that is no power
        of two: the ``(group, q block)`` grid axis of the kv-major
        kernels is ``7 * nq``.  Window and full kernels, forward and both
        backward forms, over several blocks and a padded tail, against the
        masked reference."""
        counted = self._blocks(monkeypatch, backward)
        if window is None:
            counted = counted.removeprefix("win_")
        q, k, v = _qkv(b=1, s=200, hq=14, hkv=2, d=16)
        w = jax.random.normal(jax.random.PRNGKey(7), q.shape)
        flash = functools.partial(flash_attention, interpret=True, window=window)
        masked = functools.partial(mha_reference, window=window)
        assert jnp.allclose(flash(q, k, v), masked(q, k, v), atol=2e-5)
        got, built = _flash_bwd_built(lambda: _grads(flash, q, k, v, w))
        assert built == {counted: 1}
        for name, g, r in zip("qkv", got, _grads(masked, q, k, v, w)):
            assert jnp.allclose(g, r, atol=1e-4), name

    @pytest.mark.parametrize("window", [200, 1000])
    def test_a_window_that_holds_the_sequence_is_plain_causal(
        self, window, monkeypatch
    ):
        """``window >= T``: bit for bit the plain kernels' results, and no
        banded kernel is built."""
        self._blocks(monkeypatch, "fused")
        q, k, v = _qkv(b=1, s=200, hq=4, hkv=2)
        w = jnp.ones_like(q)
        plain = functools.partial(flash_attention, interpret=True)
        wide = functools.partial(plain, window=window)
        assert (plain(q, k, v) == wide(q, k, v)).all()
        got, built = _flash_bwd_built(lambda: _grads(wide, q, k, v, w))
        assert built == {"fused": 1}
        for g, r in zip(got, _grads(plain, q, k, v, w)):
            assert (g == r).all()

    def test_window_needs_causal_and_ring_knows_none(self):
        q, k, v = _qkv()
        for fn in (flash_attention, mha_reference, attention):
            kw = {"interpret": True} if fn is flash_attention else {}
            if fn is not mha_reference:
                with pytest.raises(ValueError, match="window"):
                    fn(q, k, v, causal=False, window=8, **kw)
        with pytest.raises(ValueError, match="window"):
            attention(q, k, v, window=0, impl="jnp")
        mesh = make_mesh(MeshSpec(sp=8))
        with pytest.raises(NotImplementedError, match="window"):
            attention(q, k, v, window=8, impl="ring", mesh=mesh, seq_axis="sp")
        assert jnp.allclose(
            attention(q, k, v, window=8, impl="jnp"),
            mha_reference(q, k, v, window=8),
        )

    def test_window_under_a_mesh_runs_per_shard(self):
        """The shard_map wrapper hands the window on (batch over dp, heads
        over tp): the band is each shard's own."""
        from torchdistx_tpu.ops.pallas.flash_attention import (
            flash_attention_sharded,
        )

        mesh = make_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
        q, k, v = _qkv(b=2, s=96, hq=4, hkv=2)
        out = flash_attention_sharded(
            q, k, v, mesh=mesh, interpret=True, window=24
        )
        assert jnp.allclose(out, mha_reference(q, k, v, window=24), atol=2e-5)

    @pytest.mark.parametrize("backward", ["fused", "pair"])
    def test_blocks_outside_the_band_never_run(self, backward, monkeypatch):
        """8 blocks of 64 positions under a window of 2 blocks: every
        kernel's streamed grid axis spans the band, at most 3 blocks (the
        diagonal's, one inside, the lower edge's), not the sequence's 8,
        forward and backward; the histogram says so too."""
        from torchdistx_tpu import telemetry

        self._blocks(monkeypatch, backward)
        groups, seq, window = 2, 512, 128
        q = jax.ShapeDtypeStruct((1, seq, groups, 16), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, seq, 1, 16), jnp.float32)
        h0 = telemetry.histograms().get("attention.window_kv_blocks", {})

        def grids_of(**kw):
            jaxpr = jax.make_jaxpr(
                jax.grad(
                    lambda q, k, v: flash_attention(
                        q, k, v, interpret=True, **kw
                    ).sum(),
                    argnums=(0, 1, 2),
                )
            )(q, kv, kv)
            return {
                eqn.params["name"]: eqn.params["grid_mapping"].grid
                for eqn in jaxpr.jaxpr.eqns
                if eqn.primitive.name == "pallas_call"
            }

        grids = grids_of(window=window)
        h1 = telemetry.histograms()["attention.window_kv_blocks"]
        assert h1["count"] == h0.get("count", 0) + 1 and h1["max"] >= 3
        # q-major: (batch, q heads, q blocks, kv blocks of the band)
        assert grids["flash_win_fwd"] == (1, groups, 8, 3)
        if backward == "pair":
            assert grids["flash_win_bwd_dq"] == (1, groups, 8, 3)
            # kv-major: (batch, kv heads, kv blocks, groups x q blocks of
            # the band)
            assert grids["flash_win_bwd_dkv"] == (1, 1, 8, groups * 3)
        else:
            assert grids["flash_win_bwd_fused"] == (1, 1, 8, groups * 3)
        # the plain kernels span the sequence
        assert grids_of()["flash_fwd"] == (1, groups, 8, 8)

    @pytest.mark.parametrize(
        "seq,hq,hkv,d,pinned",
        [
            # the full layer of a 32-on-4 window/full model at 8k: the
            # streamed pair, as commit 80945ec (PR 32) lowers it
            (8192, 32, 4, 128, "4b1bf048e1b8d1ca0ce5a187ef079f020c45c3392c3fc1928fb468c1595931c6"),
        ],
    )
    def test_window_none_lowers_as_before(
        self, seq, hq, hkv, d, pinned, monkeypatch
    ):
        """Without a window the kernels are the ones the parent had: the
        gradient's StableHLO lowered for the TPU, each Mosaic body without
        its source locations, hashes to what the parent commit gives (the
        hashes of ``test_equal_widths_lower_to_the_program_of_pr_26`` hold
        the other two backward kernels), no banded kernel is named, and
        the three calls take the operands they took."""
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        q = jax.ShapeDtypeStruct((1, seq, hq, d), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, seq, hkv, d), jnp.bfloat16)
        grad = jax.grad(
            lambda q, k, v: flash_attention(q, k, v).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        )
        text = jax.jit(grad).trace(q, kv, kv).lower(
            lowering_platforms=("tpu",)
        ).as_text()
        assert "flash_win_" not in text
        assert text.count("tpu_custom_call") == 3
        assert hashlib.sha256(_without_locations(text).encode()).hexdigest() == pinned
