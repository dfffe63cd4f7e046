"""Jamba family (state-space layers, one attention layer a period): the
benchmark's plain reference against ``transformers``, the family against
the reference through both scan implementations, the selective scan's
``custom_vjp`` against ``jax.grad`` of the plain recurrence, the 20-on-1
multi-query grouping through the flash kernels, the names a trace is read
by, and the paper's path for the fills only this family has.

CPU, float32, seeded: values and counts only.
"""

import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import _common, convert, jamba
from torchdistx_tpu.ops.attention import mha_reference
from torchdistx_tpu.ops.pallas.flash_attention import flash_attention
from torchdistx_tpu.ops.selective_scan import selective_scan

import _families  # puts benchmarks/ on sys.path
from benchlib import ssm
from families import jamba as family
from reference import common, jamba as ref

CONFIG = "ai21-jamba2-3b"
SHAPE = (2, 40)


def _unsettle(params, seed=7):
    """The initializers give ``A_log`` the same row in every channel,
    ``D`` and the norms 1 and the biases 0: move each, so that a leaf
    read from the wrong place or left out shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return tree.unflatten([
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype)
        if a.ndim <= 3 else a
        for a, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module")
def tiny():
    """Sizes, native config and seeded parameters: two periods of four
    layers, the attention layer second."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    assert (cfg.n_periods, cfg.stacks) == (2, {"mamba_a": 1, "mamba_b": 2})
    return sizes, cfg, _unsettle(jamba.init_params(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def reference(tiny):
    """The plain reference's loss and gradients at the tiny parameters,
    evaluated once a shape of tokens."""
    sizes, _, params = tiny
    return _families.reference_at(ref, sizes, params)


# ---------------------------------------------------------------------------
# The reference is the published module; the family is the reference.


def test_reference_matches_transformers():
    """The plain reference's logits are those of ``JambaForCausalLM``
    (``use_mamba_kernels=False``, eager attention) on converted weights,
    and so are the native family's through both scans."""
    import torch

    sizes = _families.sizes(CONFIG)
    build, hf_config = family.hf(sizes)
    assert hf_config.use_mamba_kernels is False
    hf_config._attn_implementation = "eager"
    torch.manual_seed(0)
    module = build(hf_config).eval()
    with torch.no_grad():
        for name, p in module.named_parameters():
            if p.ndim == 1 or name.endswith("A_log"):
                p.add_(0.1 * torch.randn_like(p))
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {k: v.detach().numpy() for k, v in module.named_parameters()}
    params = convert.jamba_params_from_hf(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, jamba.abstract_params(cfg)
    )
    tokens, _ = _families.tokens(sizes, SHAPE)
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).logits.numpy()
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        got = ref.head(params, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=0)
    for impl in ("jnp", "pallas"):
        out = jamba.forward(
            params, tokens, dataclasses.replace(cfg, scan_impl=impl),
            attn_impl="jnp",
        )
        np.testing.assert_allclose(
            np.asarray(out), want, atol=1e-4, rtol=0, err_msg=impl
        )


# 40 positions in chunks of 32: the last chunk is padded; 64: two whole ones.
@pytest.mark.parametrize("seq", [40, 64])
@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(
    tiny, reference, impl, remat, seq
):
    """Loss and every gradient, through the ``jnp`` scan with jnp attention
    and through the interpreted kernels (scan and flash), with and without
    the blocks' remat, across a period boundary."""
    sizes, cfg, params = tiny
    cfg = dataclasses.replace(cfg, remat=remat, scan_impl=impl)
    tokens, targets = _families.tokens(sizes, (2, seq))
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: jamba.loss_fn(p, tokens, targets, cfg, attn_impl=impl)
    ))(params)
    _families.assert_matches_reference(loss, grads, *reference(tokens.shape))


def test_row_blocked_head_is_the_whole_head(tiny, monkeypatch):
    """The loss in row blocks is ``llama._head_ce``'s loss
    and gradient (the tied embedding's included)."""
    sizes, cfg, params = tiny
    tokens, targets = _families.tokens(sizes, (2, 64))
    whole = jax.value_and_grad(
        lambda p: jamba.loss_fn(p, tokens, targets, cfg, attn_impl="jnp")
    )(params)
    monkeypatch.setattr(_common, "_HEAD_ROWS", 32)  # four blocks
    blocked = jax.value_and_grad(
        lambda p: jamba.loss_fn(p, tokens, targets, cfg, attn_impl="jnp")
    )(params)
    assert abs(float(whole[0]) - float(blocked[0])) < 1e-5
    for a, b in zip(jax.tree.leaves(whole[1]), jax.tree.leaves(blocked[1])):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def test_parameters_are_counted_as_the_configuration_file_states():
    """One period at published widths: 1,598,556,096 parameters; the
    whole model 3,029.3M; the matrices a token multiplies 1,596,948,480."""
    sizes = _families.published(CONFIG)
    mod, cfg = family.native(sizes, jnp.bfloat16)
    assert mod is jamba and (cfg.n_periods, cfg.d_inner) == (1, 5120)
    assert jamba.num_params(cfg) == 1_598_556_096
    assert "1,598,556,096" in sizes["parameters"]
    whole = dataclasses.replace(cfg, n_layers=sizes["published"]["num_hidden_layers"])
    assert round(jamba.num_params(whole) / 1e5) == 30293
    counts = family.counts(sizes)
    assert counts["matmul_params"] == 13 * 104_038_400 + 76_677_120 + 167_772_160
    assert (counts["n_layers"], counts["n_mamba_layers"]) == (1, 13)
    # the scan's required bytes: 8 wide and 6 narrow passes a layer
    wide, narrow = 8192 * 5120 * 2, 8192 * 16 * 2
    assert ssm.selective_scan_bytes(1, 8192, 5120, 16, 13, 2) == 13 * (
        8 * wide + 6 * narrow + 3 * (5120 * 16 * 4 + 5120 * 2)
    )
    assert list(sizes["reduced"]) == ["num_hidden_layers"]


# ---------------------------------------------------------------------------
# The selective scan against the plain recurrence.


def _plain_scan(u, dt, a, b, c, d, reset_every=None):
    """One ``lax.scan`` step a position; ``reset_every``: the FAULT of a
    state that does not survive a chunk boundary."""

    def step(h, x):
        i, u_t, dt_t, b_t, c_t = x
        if reset_every:
            h = jnp.where(i % reset_every == 0, 0.0, h)
        h = jnp.exp(dt_t[..., None] * a) * h + (dt_t * u_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1)

    h0 = jnp.zeros((u.shape[0], u.shape[2], a.shape[1]))
    xs = (jnp.arange(u.shape[1]),) + tuple(
        x.swapaxes(0, 1) for x in (u, dt, b, c)
    )
    return jax.lax.scan(step, h0, xs)[1].swapaxes(0, 1) + d * u


def _scan_args(bsz, t, ch, n, shift, seed=0):
    """``shift``: delta = softplus(N(0, 1) - shift); at 5 a state keeps
    99% of itself a position, so it must survive several chunks."""
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (
        jax.random.normal(k[0], (bsz, t, ch)),
        jax.nn.softplus(jax.random.normal(k[1], (bsz, t, ch)) - shift),
        -jnp.exp(0.5 * jax.random.normal(k[2], (ch, n))),
        jax.random.normal(k[3], (bsz, t, n)),
        jax.random.normal(k[4], (bsz, t, n)),
        jax.random.normal(k[5], (ch,)),
    ), jax.random.normal(k[6], (bsz, t, ch))


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


SCAN_CASES = {
    # name: (rows, positions, channels, states, chunk, delta's shift)
    "padded_last_chunk": (2, 40, 128, 16, 16, 0.0),
    "state_survives_four_chunks": (1, 64, 256, 16, 16, 5.0),
    "one_short_chunk": (1, 24, 64, 8, 32, 0.0),
    "two_channel_blocks": (1, 32, 2048, 16, 16, 2.0),
}


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("case", SCAN_CASES)
def test_selective_scan_matches_the_plain_recurrence(case, impl):
    """``y`` and all six gradients of the chunked ``custom_vjp`` against
    ``jax.grad`` of one ``lax.scan`` over positions."""
    *shape, chunk, shift = SCAN_CASES[case]
    args, w = _scan_args(*shape, shift)
    scan = functools.partial(selective_scan, impl=impl, chunk=chunk)

    def grads(f):
        return jax.grad(lambda *a: (f(*a) * w).sum(), argnums=range(6))(*args)

    assert _rel(scan(*args), _plain_scan(*args)) < 1e-5
    for name, g, want in zip(
        ("u", "delta", "A", "B", "C", "D"), grads(scan), grads(_plain_scan)
    ):
        assert g.shape == want.shape and _rel(g, want) < 5e-5, name


@pytest.mark.parametrize("shift", [5.0, 0.0])
def test_a_state_reset_at_a_chunk_boundary_shows(shift):
    """The comparison above has the power it needs: a scan whose state is
    zeroed every 16 positions is far from the plain one, in ``y`` and in
    the gradient, whether a state lives long (delta small) or short."""
    args, w = _scan_args(1, 64, 256, 16, shift)
    faulty = functools.partial(_plain_scan, reset_every=16)
    assert _rel(faulty(*args), _plain_scan(*args)) > 1e-2
    g, want = (
        jax.grad(lambda u: (f(u, *args[1:]) * w).sum())(args[0])
        for f in (faulty, _plain_scan)
    )
    assert _rel(g, want) > 1e-2


def test_selective_scan_keeps_the_state_float32_under_bfloat16():
    """bfloat16 arguments, float32 state: against the float32 plain scan
    of the same (rounded) arguments the result differs by its own
    rounding only, over 512 positions of a long-lived state."""
    args, _ = _scan_args(1, 512, 128, 16, 5.0)
    low = tuple(x.astype(jnp.bfloat16) for x in args)
    low = low[:2] + (args[2],) + low[3:]  # A stays float32
    want = _plain_scan(*(x.astype(jnp.float32) for x in low))
    for impl in ("jnp", "pallas"):
        got = selective_scan(*low, impl=impl, chunk=64)
        assert got.dtype == jnp.bfloat16
        assert _rel(got.astype(jnp.float32), want) < 2 ** -7, impl


def test_selective_scan_refuses_what_it_cannot_run():
    args, _ = _scan_args(1, 16, 8, 4, 0.0)
    with pytest.raises(ValueError, match="unknown selective_scan impl"):
        selective_scan(*args, impl="cuda")
    with pytest.raises(ValueError, match="multiple of 16"):
        selective_scan(*args, chunk=24)
    with pytest.raises(ValueError, match="no sequence-parallel"):
        jamba.loss_fn(None, None, None, jamba.jamba_test(), seq_axis="sp")
    with pytest.raises(ValueError, match="whole periods"):
        jamba.JambaConfig(n_layers=15)


# ---------------------------------------------------------------------------
# The attention layer's grouping through the flash kernels.


@pytest.mark.parametrize("backward", ["pair", "fused"])
def test_flash_attention_at_twenty_query_heads_on_one(backward, monkeypatch):
    """20 query heads on 1 key/value head of 128 (interpreted), three
    blocks of 128: forward and the streamed backward pair, which the
    cell's shapes take (their f32 dq is 80 MiB), against ``mha_reference``;
    the one-kernel backward beside it."""
    from torchdistx_tpu.ops.pallas import flash_attention as fa

    for name in ("_BWD_BLOCK_Q", "_BWD_BLOCK_KV", "_FWD_BLOCK_KV"):
        monkeypatch.setattr(fa, name, 128)
    if backward == "pair":
        monkeypatch.setattr(fa, "_FUSED_BWD_DQ_VMEM", 0)
    ks = jax.random.split(jax.random.PRNGKey(20), 4)
    q = jax.random.normal(ks[0], (1, 384, 20, 128))
    k = jax.random.normal(ks[1], (1, 384, 1, 128))
    v = jax.random.normal(ks[2], (1, 384, 1, 128))
    w = jax.random.normal(ks[3], (1, 384, 20, 128))

    def grads(f):
        return jax.grad(lambda *a: (f(*a) * w).sum(), argnums=(0, 1, 2))(q, k, v)

    flash = functools.partial(flash_attention, causal=True, interpret=True)
    c0 = telemetry.counters()
    got = grads(flash)
    c1 = telemetry.counters()
    key = "attention.flash_bwd{kernel=%s}" % backward
    assert c1.get(key, 0) - c0.get(key, 0) == 1
    want = functools.partial(mha_reference, causal=True)
    assert jnp.allclose(flash(q, k, v), want(q, k, v), atol=2e-5)
    for name, g, r in zip("qkv", got, grads(want)):
        assert g.shape == r.shape, name
        assert jnp.allclose(g, r, atol=2e-4), name


def test_the_cells_attention_takes_the_streamed_pair():
    """From the shapes alone: 20 x 8,192 x 128 float32 of dq is past the
    one kernel's VMEM budget, at 4,096 positions too."""
    for seq in (4096, 8192):
        spec = jax.ShapeDtypeStruct((1, seq, 20, 128), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, seq, 1, 128), jnp.bfloat16)
        c0 = telemetry.counters()
        jax.make_jaxpr(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, interpret=True
            ).astype(jnp.float32).sum()
        ))(spec, kv, kv)
        c1 = telemetry.counters()
        key = "attention.flash_bwd{kernel=pair}"
        assert c1.get(key, 0) - c0.get(key, 0) == 1


# ---------------------------------------------------------------------------
# Names, the paper's path, the train step.


def test_scopes_and_counters():
    """The names a trace is read by: ``mamba`` with its five parts and the
    scan kernels under ``mamba/scan``, ``attn`` with the flash kernels,
    ``mlp``, ``embed``, ``head``; the scan's counters."""
    _, cfg = family.native(_families.sizes(CONFIG), jnp.float32)
    cfg = dataclasses.replace(cfg, scan_impl="pallas")
    params = jax.eval_shape(lambda: jamba.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    text = jax.jit(
        jax.grad(lambda p, t: jamba.loss_fn(p, t, t, cfg, attn_impl="pallas"))
    ).lower(params, tok).as_text(debug_info=True)
    c1, h1 = telemetry.counters(), telemetry.histograms()
    for scope in ("mamba/in_proj", "mamba/conv", "mamba/ssm_params",
                  "mamba/scan", "mamba/out_proj", "attn", "mlp", "embed", "head"):
        # ``.../mamba/scan/...``, or ``jvp(embed)/...`` at the top level
        assert re.search(rf"[/(]{scope}\)*/", text), scope
    assert "mamba/scan/ssm_scan_fwd" in text and "ssm_scan_bwd" in text
    assert "attn/flash_fwd/" in text

    def rose(key):
        return c1.get(key, 0) - c0.get(key, 0)

    assert rose("ssm.layers") == cfg.n_layers - cfg.n_periods == 6
    assert rose("ssm.scan{impl=pallas}") > 0 and rose("ssm.scan{impl=jnp}") == 0
    assert rose("ssm.scan{interpret=true}") == rose("ssm.scan{impl=pallas}")
    chunks = h1["ssm.scan_chunks"]
    assert chunks["count"] > h0.get("ssm.scan_chunks", {}).get("count", 0)
    assert chunks["max"] >= 64 // cfg.scan_chunk == 2


def test_deferred_init_materializes_the_state_space_fills():
    """The paper's path: ``A_log`` comes out as ``log(1..N)`` in every
    channel, ``D`` as ones, the grouped convolution's taps drawn, and no
    parameter falls back to torch."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, hf_config = family.hf(sizes)
    module = di.deferred_init(build, hf_config)
    _, cfg = family.native(sizes, jnp.float32)
    # the head is tied: the module counts the embedding once, as we do
    assert sum(p.numel() for p in module.parameters()) == jamba.num_params(cfg)
    c0 = telemetry.counters()
    arrays = M.materialize_module_jax(module, seed=3, dtype=torch.float32)
    c1 = telemetry.counters()
    key = "materialize.torch_fallback_params"
    assert c1.get(key, 0) == c0.get(key, 0)
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, jamba.abstract_params(cfg)
    )
    row = np.log(np.arange(1, cfg.d_state + 1, dtype=np.float32))
    for name, n in cfg.stacks.items():
        leaves = params["periods"][name]
        assert leaves["a_log"].shape == (2, n, cfg.d_inner, cfg.d_state)
        np.testing.assert_allclose(
            leaves["a_log"], np.broadcast_to(row, leaves["a_log"].shape),
            rtol=1e-6,
        )
        assert (np.asarray(leaves["d"]) == 1).all()
        assert not np.asarray(leaves["b_dt"]).any()
        taps = np.asarray(leaves["conv_w"])
        assert taps.shape == (2, n, cfg.d_conv, cfg.d_inner) and taps.std() > 0
    want = jamba.init_params(jax.random.PRNGKey(0), cfg)
    for name in ("a_log", "d", "b_dt", "dt_norm"):  # the same fills
        np.testing.assert_allclose(
            params["periods"]["mamba_b"][name],
            want["periods"]["mamba_b"][name], rtol=1e-6,
        )


def test_no_row_of_the_embedding_is_a_pad_row():
    """The traffic draws every row, so the module is built with no pad
    token: ``transformers`` would zero that row, and a zero row stays zero
    through every Mamba layer before the first attention layer (the
    ``silu(z)`` gate is 0 there), each of whose norms multiplies the
    position's gradient by ``eps**-0.5``."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, hf_config = family.hf(sizes)
    assert hf_config.pad_token_id is None
    arrays = M.materialize_module_jax(
        di.deferred_init(build, hf_config), seed=3, dtype=torch.float32
    )
    _, cfg = family.native(sizes, jnp.float32)
    params = family.to_params(arrays, cfg)
    rows = np.linalg.norm(np.asarray(params["embed"]["weight"]), axis=1)
    assert rows.min() > 0.5 * np.median(rows)

    # What a pad row did: three Mamba layers before the attention layer.
    cfg = dataclasses.replace(cfg, n_layers=4, attn_offset=3)
    params = jamba.init_params(jax.random.PRNGKey(0), cfg)
    tokens, targets = _families.tokens(sizes, (1, 32), seed=2)
    tokens = jnp.maximum(tokens, 1).at[0, 9].set(0)  # token 0 once

    def row_norms(p):
        g = jax.grad(jamba.loss_fn)(p, tokens, targets, cfg)
        return np.linalg.norm(np.asarray(g["embed"]["weight"]), axis=1)

    drawn = row_norms(params)
    params["embed"]["weight"] = params["embed"]["weight"].at[0].set(0)
    zeroed = row_norms(params)
    assert drawn[0] < 3 * np.median(drawn[np.unique(tokens)])
    assert zeroed[0] > 5 * drawn[0]  # 1e7 x at 7 layers of width 2560


@pytest.mark.parametrize(
    "impl,axes",
    [
        ("auto", dict(dp=2, fsdp=2, tp=2)),  # jnp off a TPU: plain XLA
        ("pallas", dict(dp=2, tp=2)),  # the kernels, a scan a shard
        ("pallas", dict(fsdp=4)),
    ],
)
def test_train_step_on_a_mesh_is_the_one_chip_step(impl, axes):
    """``make_train_step(model=jamba)`` over a mesh (the kernels per
    shard under ``shard_map``: rows over dp x fsdp, channels over tp): the
    first step's loss is the unsharded loss, and three steps bring it
    down."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(jamba.jamba_test(), scan_impl=impl)
    n = int(np.prod(list(axes.values())))
    mesh = make_mesh(MeshSpec(**axes), devices=jax.devices()[:n])
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=jamba, attn_impl="jnp"
    )
    state = init_fn(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    want = jamba.loss_fn(
        jax.device_get(state.params), tokens, tokens, cfg, attn_impl="jnp"
    )
    sharded = jax.device_put(tokens, ts.batch_sharding(mesh))
    losses = []
    for _ in range(3):
        state, metrics = step_fn(state, {"tokens": sharded, "targets": sharded})
        losses.append(float(metrics["loss"]))
    assert abs(losses[0] - float(want)) < 1e-4
    assert losses[2] < losses[0] and not bool(metrics["nonfinite"])
    assert jax.tree.structure(jamba.param_specs(cfg)) == jax.tree.structure(
        jamba.abstract_params(cfg)
    )


def test_the_kernels_refuse_a_mesh_they_were_not_verified_on():
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(jamba.jamba_test(), scan_impl="pallas")
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    params = jax.eval_shape(lambda: jamba.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((4, 32), jnp.int32)
    with pytest.raises(NotImplementedError, match="not verified"):
        jax.eval_shape(
            lambda p, t: jamba.loss_fn(p, t, t, cfg, mesh=mesh, attn_impl="jnp"),
            params, tok,
        )


def test_models_package_exports_the_family():
    from torchdistx_tpu import models

    assert models.jamba is jamba and "jamba" in models.__all__
