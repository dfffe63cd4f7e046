"""AFMoE family (window and full attention layers mixed, per-head q/k norms,
a sigmoid output gate, sandwich norms, routed experts told which they
hold): the benchmark's plain reference against the published architecture
in torch, the family against the reference, the share against the whole,
each mechanism's absence seen by the loss, and the paper's path with the
absent experts dropped before materialization.

CPU, float32, seeded: values and counts only.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import afmoe, convert
from torchdistx_tpu.models import deepseek_v3 as ds
from torchdistx_tpu.models import llama as llama_mod

import _families  # puts benchmarks/ on sys.path
from families import afmoe as family
from reference import afmoe as ref
from reference import common

CONFIG = "trinity-mini"
SHAPE = (2, 80)


def _seeded(cfg, seed=0):
    """Seeded parameters with norms off one and a selection bias off zero,
    so that no norm's weight and no bias can drop out unseen."""
    params = afmoe.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 9), 64))
    for stack in ("dense_layers", "moe_layers"):
        for name, a in params[stack].items():
            if name.endswith("norm"):
                params[stack][name] = a + 0.2 * jax.random.normal(
                    next(keys), a.shape
                )
    params["moe_layers"]["router_bias"] = 0.05 * jax.random.normal(
        next(keys), params["moe_layers"]["router_bias"].shape
    )
    return params


SHARES = {
    "whole": dict(num_experts=8, first_expert_held=0),
    "share": {},  # the file's tiny block: experts 2-5 of 8
}


@pytest.fixture(scope="module", params=list(SHARES))
def held(request):
    """Sizes, native config and seeded parameters, whole and as a share."""
    sizes = _families.sizes(CONFIG, **SHARES[request.param])
    _, cfg = family.native(sizes, jnp.float32)
    if request.param == "share":
        assert (cfg.held, cfg.first_expert_held, cfg.n_experts) == (4, 2, 8)
    assert cfg.layer_types == (afmoe.WINDOW, afmoe.WINDOW, afmoe.FULL)
    return sizes, cfg, _seeded(cfg)


@pytest.fixture(scope="module")
def reference(held):
    """The plain reference's loss and gradients at the held parameters,
    evaluated once a shape of tokens."""
    sizes, _, params = held
    return _families.reference_at(ref, sizes, params)


@pytest.mark.parametrize("seq", [40, 80])
def test_reference_matches_the_torch_module(seq):
    """Every expert held: the plain reference's logits are those of the
    published architecture in torch on the same weights; at 80 positions
    the band's lower edge (window 48) is crossed, at 40 it is not."""
    import torch

    sizes = _families.sizes(CONFIG, num_experts=8, first_expert_held=0)
    build, torch_config = family.hf(sizes)
    torch.manual_seed(0)
    module = build(torch_config).eval()
    with torch.no_grad():
        for name, p in module.named_parameters():
            if name.endswith("norm.weight") or "layernorm" in name:
                p.add_(0.2 * torch.randn_like(p))
            if name.endswith("expert_bias"):
                p.normal_(0.0, 0.05)
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {k: v.detach().numpy() for k, v in module.state_dict().items()}
    params = convert.afmoe_params_from_hf(arrays, cfg)
    tokens, _ = _families.tokens(sizes, (2, seq))
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).numpy()
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        got = ref.head(params, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=0)
    # ... and so are the native family's, every expert held
    np.testing.assert_allclose(
        np.asarray(afmoe.forward(params, tokens, cfg, attn_impl="jnp")), want,
        atol=2e-4, rtol=0,
    )


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(held, reference, impl, remat):
    """Whole and as a share: loss and every gradient, through jnp attention
    and the interpreted flash kernels (banded in the window layers), with
    and without remat."""
    sizes, cfg, params = held
    cfg = dataclasses.replace(cfg, remat=remat)
    tokens, targets = _families.tokens(sizes, SHAPE)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: afmoe.loss_fn(p, tokens, targets, cfg, attn_impl=impl),
        has_aux=True,
    )(params)
    _families.assert_matches_reference(loss, grads, *reference(tokens.shape))
    # the selection bias is a buffer: no gradient moves it
    assert not np.asarray(grads["moe_layers"]["router_bias"]).any()
    n = tokens.size * cfg.experts_per_token * cfg.n_moe_layers
    assigned = float(aux["moe"]["local_assignments"])
    assert assigned == n if cfg.held == cfg.n_experts else 0 < assigned < n
    assert float(aux["moe"]["load_max_over_mean"]) >= 1.0


def test_four_shares_add_up_to_the_whole_layer():
    """32 experts in four shares of 8: the routed parts summed, the shared
    expert counted once, are the uncut reference's expert sub-block."""
    sizes = _families.sizes(
        CONFIG, num_experts=32, num_experts_total=32, first_expert_held=0,
        num_experts_per_tok=8,
    )
    _, cfg = family.native(sizes, jnp.float32)
    params = afmoe.init_params(jax.random.PRNGKey(2), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    h = jax.random.normal(jax.random.PRNGKey(4), (2, 40, cfg.dim))
    shared = ds._swiglu(h, lp["s_gate"], lp["s_up"], lp["s_down"])
    total, assigned = shared, 0.0
    for first in range(0, 32, 8):
        part = dict(lp, **{
            k: lp[k][first:first + 8] for k in ("e_gate", "e_up", "e_down")
        })
        out, stats = ds.moe_block(
            h, part, dataclasses.replace(
                cfg, n_experts_held=8, first_expert_held=first
            ),
        )
        total = total + (out - shared)
        assigned += float(stats["local_assignments"])
    with common.precision(jnp.float32):
        want = ref.routed(h, lp, sizes) + shared
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert assigned == h.shape[0] * h.shape[1] * 8  # every choice, once


def _without(monkeypatch, what):
    """The program with one mechanism taken out."""
    if what == "rope_on_window":
        monkeypatch.setattr(llama_mod, "_rope_apply", lambda x, cos, sin: x)
    elif what == "no_rope_on_full":
        # every layer takes the window layers' path, behind a window that
        # holds the whole sequence: rope reaches the full layer, no mask moves
        attn = afmoe._attn
        monkeypatch.setattr(
            afmoe, "_attn",
            lambda x, lp, cfg, kind, **kw: attn(
                x, lp, cfg if kind == afmoe.WINDOW
                else dataclasses.replace(cfg, window=1 << 20),
                afmoe.WINDOW, **kw
            ),
        )
    elif what == "window":
        attention = afmoe.attention
        monkeypatch.setattr(
            afmoe, "attention",
            lambda *a, window=None, **kw: attention(*a, **kw),
        )
    elif what == "gate":
        monkeypatch.setattr(afmoe, "_gated", lambda a, g: a)
    elif what in ("q_norm", "k_norm", "post_attn_norm", "post_mlp_norm"):
        rms = llama_mod._rmsnorm
        leaf = {}

        def rmsnorm(x, weight, eps):
            return x if weight is leaf.get("w") else rms(x, weight, eps)

        monkeypatch.setattr(llama_mod, "_rmsnorm", rmsnorm)
        return lambda lp: leaf.__setitem__("w", lp[what])
    else:
        assert what == "embed_scale"
    return lambda lp: None


@pytest.mark.parametrize("what", [
    "rope_on_window", "no_rope_on_full", "window", "gate", "q_norm", "k_norm",
    "post_attn_norm", "post_mlp_norm", "embed_scale",
])
def test_rope_only_in_window_layers_and_gate_and_norms(monkeypatch, what):
    """Each mechanism taken out of the program moves the loss past the
    tolerance that holds the program to the reference (1e-5), so none can
    be left out unseen."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    cfg = dataclasses.replace(cfg, remat=False)  # leaves stay themselves
    params = _seeded(cfg)
    # weights large enough for every sub-block to move the logits
    params = jax.tree.map(lambda a: a * 4.0 if a.ndim > 2 else a, params)
    params["lm_head"]["weight"] = params["lm_head"]["weight"] * 30.0
    tokens, targets = _families.tokens(sizes, SHAPE)
    want = float(_families.ref_loss(ref, params, tokens, targets, sizes))
    got = float(afmoe.loss_fn(params, tokens, targets, cfg, attn_impl="jnp")[0])
    assert abs(got - want) < 1e-5
    if what == "embed_scale":
        cfg = dataclasses.replace(cfg, embed_scale=False)
    mark = _without(monkeypatch, what)

    # Without jit: the patched norm finds its layer's leaf by identity.
    def loss(p):
        x = llama_mod._embed(p, tokens, cfg)
        if cfg.embed_scale:
            x = x * (cfg.dim ** 0.5)
        dense, moe = afmoe._build_blocks(cfg, attn_impl="jnp")
        kinds = cfg.layer_types
        for i, kind in enumerate(kinds):
            stack = "dense_layers" if i < cfg.n_dense_layers else "moe_layers"
            j = i if i < cfg.n_dense_layers else i - cfg.n_dense_layers
            lp = jax.tree.map(lambda a: a[j], p[stack])
            mark(lp)
            block = (dense if stack == "dense_layers" else moe)(kind)
            x, _ = block(x, lp)
        return llama_mod._head_ce(p, x, targets, cfg)

    assert abs(float(loss(params)) - want) > 1e-4, what


def test_layer_kinds_are_static_and_the_published_pattern_runs():
    """Two dense layers and six expert layers in the published pattern
    (every fourth layer full): the expert stack's kinds start mid-period
    and end off a period's boundary, and the program still agrees with the
    reference; each window layer builds a banded call, each full layer a
    plain one."""
    sizes = _families.sizes(
        CONFIG, num_hidden_layers=8, num_dense_layers=2, first_full_layer=3,
        layer_types=["sliding_attention"] * 3 + ["full_attention"]
        + ["sliding_attention"] * 3 + ["full_attention"],
    )
    _, cfg = family.native(sizes, jnp.float32)
    assert cfg == dataclasses.replace(cfg, layer_types=None)  # the default
    assert afmoe._period(cfg.layer_types[2:]) == 4
    params = _seeded(cfg)
    tokens, targets = _families.tokens(sizes, SHAPE)
    c0 = telemetry.counters()
    loss, _ = afmoe.loss_fn(params, tokens, targets, cfg, attn_impl="pallas")
    c1 = telemetry.counters()
    want = _families.ref_loss(ref, params, tokens, targets, sizes)
    assert abs(float(loss) - float(want)) < 1e-5
    # counted per trace (a scan's body once, a block met again from the
    # cache): some calls banded, and some not
    window = c1["attention.flash_window{window=48}"] - c0.get(
        "attention.flash_window{window=48}", 0
    )
    flash = "attention.flash{interpret=true}"
    assert 0 < window < c1[flash] - c0.get(flash, 0)


def test_absent_experts_are_never_materialized():
    """The paper's path: the layer is constructed with every expert, fake;
    the absent ones are dropped; materialization fills the share's
    parameters and no more."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, torch_config = family.hf(sizes)
    module = di.deferred_init(build, torch_config)
    _, cfg = family.native(sizes, jnp.float32)
    assert sum(p.numel() for p in module.parameters()) == afmoe.num_params(cfg)
    full = dataclasses.replace(cfg, n_experts_held=None)
    assert afmoe.num_params(full) - afmoe.num_params(cfg) == (
        cfg.n_moe_layers * 4 * 3 * cfg.dim * cfg.expert_dim
    )
    c0 = telemetry.counters()
    arrays = M.materialize_module_jax(module, seed=3, dtype=torch.float32)
    c1 = telemetry.counters()
    ran = {
        k: c1[k] - c0.get(k, 0) for k in c1
        if k.startswith("materialize.") and c1[k] != c0.get(k, 0)
    }
    assert not any("experts.4." in k for k in arrays)
    # norms and the selection bias are made of ones and zeros: no fill
    want_fills = (
        2  # embed, head
        + cfg.n_layers * 5 + cfg.n_dense_layers * 3  # the five projections
        + cfg.n_moe_layers * (1 + 3 + 3 * cfg.held)  # router, shared, held
    )
    assert ran.get("materialize.fill_fastpath_hits") == want_fills
    assert ran.get("materialize.torch_fallback_params", 0) == 0
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, afmoe.abstract_params(cfg)
    )
    assert not np.asarray(params["moe_layers"]["router_bias"]).any()
    assert (np.asarray(params["moe_layers"]["q_norm"]) == 1).all()
    assert np.asarray(params["moe_layers"]["router"]).std() > 0.01


def test_scopes_and_counters():
    """The names a trace is read by: ``attn`` with ``qk_norm``, ``rope``,
    ``gate`` and the kernels under it (banded in the window layers, plain
    in the full one), ``mlp``, ``moe/router|dispatch|experts|combine|
    shared``; the host counters of the share and of the window."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    params = jax.eval_shape(lambda: afmoe.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    text = jax.jit(
        jax.grad(lambda p, t: afmoe.loss_fn(p, t, t, cfg, attn_impl="pallas")[0])
    ).lower(params, tok).as_text(debug_info=True)
    c1, h1 = telemetry.counters(), telemetry.histograms()
    for scope in ("attn", "attn/qk_norm", "attn/rope", "attn/gate", "mlp",
                  "moe/router", "moe/dispatch", "moe/experts", "moe/combine",
                  "moe/shared"):
        assert f"{scope}/" in text, scope
    assert "(embed)/" in text and "(head)/" in text  # outside every block
    assert "attn/flash_win_fwd/" in text and "flash_win_bwd_fused/" in text
    assert "attn/flash_fwd/" in text and "flash_bwd_fused/" in text

    def rose(name):
        return c1.get(name, 0) - c0.get(name, 0)

    held, total = rose("moe.experts_held"), rose("moe.experts_total")
    assert held > 0 and total == 2 * held
    assert sizes["num_experts_total"] == 2 * sizes["num_experts"]
    # a trace of the routed layer's backward rule an expert layer
    assert rose("moe.first_chunk{forward=kept}") == 2
    # two window layers and one full: three flash calls, two of them banded
    assert rose("attention.flash_window{window=48}") == 2
    assert rose("attention.flash{interpret=true}") == 3
    assert rose("attention.flash_bwd{kernel=win_fused_nk1}") == 2
    assert rose("attention.flash_bwd{kernel=fused_nk1}") == 1
    hist = "attention.window_kv_blocks"
    # once a trace of a window layer's forward (the remat traces it anew)
    assert h1[hist]["count"] - h0.get(hist, {}).get("count", 0) >= 2
    assert h1[hist]["max"] >= 1


def test_train_step_takes_the_family():
    """``make_train_step`` takes ``models.afmoe`` as it takes
    ``deepseek_v3`` (``LOSS_HAS_AUX``): the loss falls, the counts come
    out, the share's stacks keep their shapes."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(
        afmoe.afmoe_test(), n_experts_held=4, first_expert_held=2
    )
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=afmoe, attn_impl="jnp"
    )
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
        ts.batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    state = init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert set(metrics["moe"]) == {
        "local_assignments", "load_max_over_mean", "row_chunks"
    }
    n = tokens.size * cfg.experts_per_token * cfg.n_moe_layers
    assert 0 < float(metrics["moe"]["local_assignments"]) < n
    assert state.params["moe_layers"]["e_gate"].shape[:2] == (3, 4)
