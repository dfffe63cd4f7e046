"""Olmo-Hybrid family (gated delta-rule linear attention and full attention
with no position term, 3 to 1 by layer, OLMo 3's post-norm block): the
benchmark's plain reference against the published architecture in torch,
the family against the reference through ``jnp`` and the interpreted
kernels, with and without remat; the paper's path (``deferred_init`` ->
``materialize_module_jax`` -> ``convert``); that the loss sees each
mechanism; the train step; the scopes and counters.

CPU, float32, seeded: values and counts only.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import convert, olmo_hybrid

import _families  # puts benchmarks/ on sys.path
from families import olmo_hybrid as family
from reference import common
from reference import olmo_hybrid as ref

CONFIG = "olmo-hybrid-7b"
SHAPE = (2, 40)
LINEAR, FULL = olmo_hybrid.LINEAR, olmo_hybrid.FULL


def _seeded(cfg, seed=0):
    """Seeded parameters with norms off one, so that no norm's weight can
    drop out unseen."""
    params = olmo_hybrid.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 9), 64))
    for stack in ("linear_layers", "full_layers"):
        for name, a in params[stack].items():
            if name.endswith("norm"):
                params[stack][name] = a + 0.2 * jax.random.normal(next(keys), a.shape)
    return params


@pytest.fixture(scope="module")
def tiny():
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    assert cfg.layer_types == (LINEAR, LINEAR, LINEAR, FULL) * 2
    return sizes, cfg, _seeded(cfg)


@pytest.fixture(scope="module")
def reference(tiny):
    """The plain reference's loss and gradients at the tiny parameters,
    evaluated once a shape of tokens."""
    sizes, _, params = tiny
    return _families.reference_at(ref, sizes, params)


def _torch_module(sizes):
    import torch

    build, torch_config = family.hf(sizes)
    torch.manual_seed(0)
    module = build(torch_config).eval()
    with torch.no_grad():
        for name, p in module.named_parameters():
            if "norm" in name or "layernorm" in name:
                p.add_(0.2 * torch.randn_like(p))
    return module


def test_reference_matches_the_torch_module():
    """The plain reference's logits are those of the published architecture
    in torch (the delta rule one position at a time there too) on the same
    weights, through ``convert``, and so are the family's."""
    import torch

    sizes = _families.sizes(CONFIG)
    module = _torch_module(sizes)
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {k: v.detach().numpy() for k, v in module.state_dict().items()}
    params = convert.olmo_hybrid_params_from_hf(arrays, cfg)
    tokens, _ = _families.tokens(sizes, SHAPE)
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).numpy()
    with common.precision(jnp.float32):
        got = ref.head(params, ref.hidden(params, tokens, sizes, jnp.float32), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=5e-5, rtol=0)
    for impl in ("jnp", "pallas"):
        np.testing.assert_allclose(
            np.asarray(olmo_hybrid.forward(params, tokens, cfg, attn_impl=impl)),
            want, atol=5e-5, rtol=0,
        )


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(tiny, reference, impl):
    """Loss and every gradient through the jnp paths and the interpreted
    kernels; two periods under the period scan, a sequence that is not a
    whole number of the delta rule's chunks.  (With remat the same bits
    to ``tests/test_remat_policy.py``'s tolerance.)"""
    sizes, cfg, params = tiny
    cfg = dataclasses.replace(cfg, remat=False)
    tokens, targets = _families.tokens(sizes, SHAPE)
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: olmo_hybrid.loss_fn(p, tokens, targets, cfg, attn_impl=impl)
    ))(params)
    _families.assert_matches_reference(loss, grads, *reference(tokens.shape))


def _altered(ref_mod, name, monkeypatch):
    """The reference with one mechanism taken out."""
    if name == "beta_factor":
        monkeypatch.setattr(ref_mod, "BETA_SCALE", 1.0)
    elif name == "conv":
        monkeypatch.setattr(ref_mod, "_conv", lambda u, w: u * w[-1])
    elif name == "gate":
        monkeypatch.setattr(ref_mod, "_out_gate", lambda o, z: o)
    elif name == "qk_norm_per_head":
        def per_head(q, k, lp, sizes):
            h = sizes["num_attention_heads"]
            eps = sizes["rms_norm_eps"]

            def norm(x, w):
                heads = x.reshape(*x.shape[:-1], h, -1)
                return ref_mod._rms(heads, 1.0, eps).reshape(x.shape) * w

            return norm(q, lp["q_norm"]), norm(k, lp["k_norm"])

        monkeypatch.setattr(ref_mod, "_qk_norm", per_head)


@pytest.mark.parametrize("mechanism", ["beta_factor", "conv", "gate", "qk_norm_per_head"])
def test_the_loss_sees_each_mechanism(tiny, mechanism, monkeypatch):
    """beta's factor 2, the convolution's taps before the last, the SiLU
    gate of the output norm, and q/k norms over the whole projection rather
    than per head: the reference without each moves the loss far past the
    family's agreement with it (1e-5)."""
    sizes, _, params = tiny
    tokens, targets = _families.tokens(sizes, SHAPE)
    whole = _families.ref_loss(ref, params, tokens, targets, sizes)
    _altered(ref, mechanism, monkeypatch)
    without = _families.ref_loss(ref, params, tokens, targets, sizes)
    assert abs(float(without) - float(whole)) > 1e-3


def test_the_paper_path_gives_the_native_tree():
    """``deferred_init`` of the torch module -> ``materialize_module_jax``
    -> ``convert``: every leaf filled, the native tree's shapes, norms of
    ones, the embedding's rows N(0, 1), the matrices N(0, 0.02), ``A_log``
    the log of U(0, 16), ``dt_bias`` ones."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, torch_config = family.hf(sizes)
    module = di.deferred_init(build, torch_config)
    _, cfg = family.native(sizes, jnp.float32)
    assert sum(p.numel() for p in module.parameters()) == olmo_hybrid.num_params(cfg)
    arrays = M.materialize_module_jax(module, seed=3, dtype=torch.float32)
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, olmo_hybrid.abstract_params(cfg)
    )
    lin = {k: np.asarray(v) for k, v in params["linear_layers"].items()}
    assert (lin["o_norm"] == 1).all() and (lin["dt_bias"] == 1).all()
    assert (lin["A_log"] < np.log(16)).all() and lin["A_log"].std() > 0.3
    assert 0.8 < float(np.asarray(params["embed"]["weight"]).std()) < 1.2
    assert 0.015 < float(lin["wq"].std()) < 0.025
    tokens, _ = _families.tokens(sizes, SHAPE)
    logits = olmo_hybrid.forward(params, tokens, cfg, attn_impl="jnp")
    assert np.isfinite(np.asarray(logits)).all()


def test_published_sizes():
    """The published layout and the cell's cut: one period's parameters
    and the three vocabulary shares the memory rule weighs."""
    full = olmo_hybrid.OlmoHybridConfig()
    assert full.layer_types.count(FULL) == 8 and full.n_layers == 32
    one = olmo_hybrid.OlmoHybridConfig(
        n_layers=4, layer_types=(LINEAR,) * 3 + (FULL,), vocab_size=0
    )
    assert olmo_hybrid.num_params(one) - 3840 == 832_520_436  # one period
    half = dataclasses.replace(one, vocab_size=50176)
    assert olmo_hybrid.num_params(half) == 1_217_875_956
    assert olmo_hybrid.num_params(dataclasses.replace(one, vocab_size=100352)) == 1_603_227_636
    with pytest.raises(ValueError, match="layer_types"):
        olmo_hybrid.OlmoHybridConfig(n_layers=4)
    with pytest.raises(ValueError, match="disagree"):
        family.native(dict(_families.sizes(CONFIG), full_attn_offset=0), jnp.float32)


def test_scopes_and_counters(tiny):
    """The names a trace is read by: ``gdn/*`` around the delta rule's two
    kernels, ``attn/qk_norm`` with the flash kernels, ``mlp``, ``stack``,
    ``embed``, ``head``; the ops' counters, three linear layers a scanned
    body."""
    sizes, cfg, _ = tiny
    params = jax.eval_shape(lambda: olmo_hybrid.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 64), jnp.int32)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    text = jax.jit(jax.grad(
        lambda p, t: olmo_hybrid.loss_fn(p, t, t, cfg, attn_impl="pallas")
    )).lower(params, tok).as_text(debug_info=True)
    c1, h1 = telemetry.counters(), telemetry.histograms()
    for scope in ("gdn/proj_in", "gdn/conv", "gdn/l2norm", "gdn/gates",
                  "gdn/delta", "gdn/out_norm", "gdn/proj_out", "attn/qk_norm",
                  "attn/proj_in", "mlp"):
        assert f"{scope}/" in text, scope
    assert "(stack)/" in text and "(embed)/" in text and "(head)/" in text
    for kernel in ("delta_rule_fwd", "delta_rule_bwd", "flash_fwd"):
        assert kernel in text, kernel

    def rose(name):
        return c1.get(name, 0) - c0.get(name, 0)

    assert rose("delta_rule{impl=pallas}") == 3
    assert rose("delta_rule{interpret=true}") == 3
    assert h1["delta_rule.chunks"]["count"] - h0.get("delta_rule.chunks", {}).get("count", 0) == 3


def test_train_step_takes_the_family():
    """``make_train_step`` takes ``models.olmo_hybrid`` as it takes the
    other families: the loss falls on a mesh of eight devices through the
    jnp paths."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = olmo_hybrid.olmo_hybrid_test()
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=olmo_hybrid, attn_impl="jnp"
    )
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
        ts.batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    state = init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(4):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
