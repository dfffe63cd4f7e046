"""SmallThinker family (every layer an expert layer whose router reads the
layer's input, before attention; ReGLU experts told which they hold; full
and window attention layers 1 to 3; a head group of three here, seven as
published): the benchmark's plain reference against the published
architecture in torch, the family against the reference, the share against
the whole, the period scan against the layers one by one, each mechanism's
absence seen by the loss, and the paper's path with the absent experts
dropped before materialization.

CPU, float32, seeded: values and counts only.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import _common, convert, smallthinker
from torchdistx_tpu.models import llama as llama_mod
from torchdistx_tpu.ops import routed_experts as routed_mod

import _families  # puts benchmarks/ on sys.path
from families import smallthinker as family
from reference import common
from reference import smallthinker as ref

CONFIG = "smallthinker-21ba3b-instruct"
SHAPE = (2, 80)
WINDOW, FULL = smallthinker.WINDOW, smallthinker.FULL


def _seeded(cfg, seed=0):
    """Seeded parameters with norms off one, so that no norm's weight can
    drop out unseen, and the layers' matrices four times as large: 0.02 is
    drawn for a width of 2,560, and at the test's 64 the blocks would add
    next to nothing to a stream of unit rows."""
    params = smallthinker.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 9), 8))
    for name, a in params["moe_layers"].items():
        if name.endswith("norm"):
            params["moe_layers"][name] = a + 0.2 * jax.random.normal(
                next(keys), a.shape
            )
        else:
            params["moe_layers"][name] = 4 * a
    return params


SHARES = {
    "whole": dict(moe_num_primary_experts=8, first_expert_held=0),
    "share": {},  # the file's tiny block: experts 2-5 of 8
}


@pytest.fixture(scope="module", params=list(SHARES))
def held(request):
    """Sizes, native config and seeded parameters, whole and as a share."""
    sizes = _families.sizes(CONFIG, **SHARES[request.param])
    _, cfg = family.native(sizes, jnp.float32)
    if request.param == "share":
        assert (cfg.held, cfg.first_expert_held, cfg.n_experts) == (4, 2, 8)
    assert cfg.layer_types == (FULL, WINDOW, WINDOW, WINDOW) * 2
    assert cfg.n_heads // cfg.n_kv_heads == 3  # not a power of two
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
    published architecture in torch on the same weights, through
    ``convert``; at 80 positions the band's lower edge (window 48) is
    crossed, at 40 it is not.  And so are the native family's."""
    import torch

    sizes = _families.sizes(CONFIG, moe_num_primary_experts=8, first_expert_held=0)
    build, torch_config = family.hf(sizes)
    torch.manual_seed(0)
    module = build(torch_config).eval()
    with torch.no_grad():
        for name, p in module.named_parameters():
            if name.endswith("norm.weight") or "layernorm" in name:
                p.add_(0.2 * torch.randn_like(p))
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {k: v.detach().numpy() for k, v in module.state_dict().items()}
    params = convert.smallthinker_params_from_hf(arrays, cfg)
    tokens, _ = _families.tokens(sizes, (2, seq))
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).numpy()
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        got = ref.head(params, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-4, rtol=0)
    np.testing.assert_allclose(
        np.asarray(smallthinker.forward(params, tokens, cfg, attn_impl="jnp")),
        want, atol=2e-4, rtol=0,
    )


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(held, reference, impl, remat):
    """Whole and as a share (``first_expert_held`` 2): loss and every
    gradient, through jnp attention and the interpreted flash kernels
    (banded in the window layers) at a group of three, with and without
    remat; the row-blocked head against the reference's whole one."""
    sizes, cfg, params = held
    cfg = dataclasses.replace(cfg, remat=remat)
    tokens, targets = _families.tokens(sizes, SHAPE)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: smallthinker.loss_fn(p, tokens, targets, cfg, attn_impl=impl),
        has_aux=True,
    )(params)
    _families.assert_matches_reference(loss, grads, *reference(tokens.shape))
    n = tokens.size * cfg.experts_per_token * cfg.n_layers
    assigned = float(aux["moe"]["local_assignments"])
    assert assigned == n if cfg.held == cfg.n_experts else 0 < assigned < n
    assert float(aux["moe"]["load_max_over_mean"]) >= 1.0


def test_the_head_goes_in_row_blocks(monkeypatch):
    """Several blocks of rows give the loss and the gradients of one."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    params = _seeded(cfg)
    tokens, targets = _families.tokens(sizes, (2, 64))

    def run():
        return jax.value_and_grad(
            lambda p: smallthinker.loss_fn(p, tokens, targets, cfg, attn_impl="jnp")[0]
        )(params)

    whole, whole_grads = run()
    monkeypatch.setattr(_common, "_HEAD_ROWS", 32)  # four blocks
    blocked, blocked_grads = run()
    assert abs(float(whole) - float(blocked)) < 1e-6
    for a, b in zip(
        jax.tree.leaves(whole_grads), jax.tree.leaves(blocked_grads), strict=True
    ):
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)


def test_four_shares_add_up_to_the_whole_layer():
    """32 experts in four shares of 8, six a token: the routed parts the
    shares give, each routed by the LAYER'S INPUT and fed the experts'
    own, summed, are the uncut reference's routed sum."""
    sizes = _families.sizes(
        CONFIG, moe_num_primary_experts=32, moe_num_primary_experts_total=32,
        first_expert_held=0, moe_num_active_primary_experts=6,
    )
    _, cfg = family.native(sizes, jnp.float32)
    params = smallthinker.init_params(jax.random.PRNGKey(2), cfg)
    lp = jax.tree.map(lambda a: a[0], params["moe_layers"])
    x, u = jax.random.normal(jax.random.PRNGKey(4), (2, 80, cfg.dim))
    routing = routed_mod.route(x, lp["router"], top_k=6)
    total, assigned = 0.0, 0.0
    for first in range(0, 32, 8):
        out, stats = routed_mod.routed_experts(
            u, lp["router"], *(
                lp[k][first:first + 8] for k in ("e_gate", "e_up", "e_down")
            ), top_k=6, first_held=first, unit="relu", routing=routing,
        )
        total = total + out
        assigned += float(stats["local_assignments"])
    with common.precision(jnp.float32):
        want = ref.routed(x, u, lp, sizes)
    np.testing.assert_allclose(total, want, atol=2e-5, rtol=0)
    assert assigned == x.shape[0] * 6  # every choice, once


def test_the_period_scan_is_the_layers_one_by_one():
    """Two periods under one scan against the eight layers unrolled, each
    by the block of its own kind: the same hidden states and counts."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    params = _seeded(cfg)
    tokens, _ = _families.tokens(sizes, SHAPE)
    x, moe = smallthinker._forward_hidden(params, tokens, cfg, attn_impl="jnp")
    block_of = smallthinker._build_block(cfg, attn_impl="jnp")
    y, assigned = llama_mod._embed(params, tokens, cfg), 0.0
    for i, kind in enumerate(cfg.layer_types):
        lp = jax.tree.map(lambda a: a[i], params["moe_layers"])
        y, stats = block_of(kind)(y, lp)
        assigned += float(stats[0])
    np.testing.assert_allclose(x, y, atol=1e-5, rtol=0)
    assert float(moe["local_assignments"]) == assigned


def _loss_with(monkeypatch, what, cfg, params, tokens, targets):
    """The family's loss with one mechanism taken out or swapped."""
    attn, routed = smallthinker._attn, smallthinker.routed_experts
    if what == "router reads the experts' input":
        monkeypatch.setattr(
            smallthinker, "routed_experts",
            lambda *a, routing=None, **kw: routed(*a, **kw),
        )
    elif what == "silu in the unit":
        monkeypatch.setattr(
            smallthinker, "routed_experts",
            lambda *a, unit=None, **kw: routed(*a, unit="silu", **kw),
        )
    elif what == "rope on the full layers":
        monkeypatch.setattr(
            smallthinker, "_attn",
            lambda x, lp, cfg, kind, **kw: attn(
                x, lp,
                cfg if kind == WINDOW else dataclasses.replace(cfg, window=1 << 30),
                WINDOW, **kw
            ),
        )
    elif what == "no rope on the window layers":
        monkeypatch.setattr(
            llama_mod, "_rope_apply", lambda x, cos, sin: x
        )
    elif what == "window layers plain causal":
        cfg = dataclasses.replace(cfg, window=1 << 30)
    return float(
        smallthinker.loss_fn(params, tokens, targets, cfg, attn_impl="jnp")[0]
    )


@pytest.mark.parametrize("what", [
    "router reads the experts' input", "silu in the unit",
    "rope on the full layers", "no rope on the window layers",
    "window layers plain causal",
])
def test_each_mechanism_is_seen_by_the_loss(monkeypatch, what):
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    params = _seeded(cfg)
    tokens, targets = _families.tokens(sizes, SHAPE)
    want = float(_families.ref_loss(ref, params, tokens, targets, sizes))
    assert abs(
        _loss_with(monkeypatch, None, cfg, params, tokens, targets) - want
    ) < 2e-6
    assert abs(
        _loss_with(monkeypatch, what, cfg, params, tokens, targets) - want
    ) > 1e-5, what


def test_the_published_layout_is_the_default():
    """52 layers: every fourth from layer 0 is full attention with no
    rope; the family refuses a configuration whose layouts and numbers
    disagree."""
    cfg = smallthinker.SmallThinkerConfig()
    assert cfg.layer_types == (FULL, WINDOW, WINDOW, WINDOW) * 13
    assert (cfg.n_heads // cfg.n_kv_heads, cfg.window, cfg.held) == (7, 4096, 64)
    from torchdistx_tpu.models import afmoe

    assert afmoe._period(cfg.layer_types) == 4
    sizes = _families.sizes(CONFIG)
    _, native = family.native(sizes, jnp.float32)
    assert native == dataclasses.replace(native, layer_types=None)
    with pytest.raises(ValueError, match="disagree"):
        family.native(dict(sizes, rope_layout=[1] * 8), jnp.float32)
    with pytest.raises(ValueError, match="disagree"):
        family.native(dict(sizes, first_full_layer=1), jnp.float32)


def test_absent_experts_are_never_materialized():
    """The paper's path: the layer is constructed with every expert, fake;
    the absent ones are dropped; materialization fills the share's
    parameters and no more; ``convert`` gives the native tree."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, torch_config = family.hf(sizes)
    module = di.deferred_init(build, torch_config)
    _, cfg = family.native(sizes, jnp.float32)
    assert sum(p.numel() for p in module.parameters()) == smallthinker.num_params(cfg)
    full = dataclasses.replace(cfg, n_experts_held=None)
    assert smallthinker.num_params(full) - smallthinker.num_params(cfg) == (
        cfg.n_layers * 4 * 3 * cfg.dim * cfg.expert_dim
    )
    c0 = telemetry.counters()
    arrays = M.materialize_module_jax(module, seed=3, dtype=torch.float32)
    c1 = telemetry.counters()
    ran = {
        k: c1[k] - c0.get(k, 0) for k in c1
        if k.startswith("materialize.") and c1[k] != c0.get(k, 0)
    }
    assert not any("experts.4." in k for k in arrays)
    # norms are made of ones: no fill.  embed, head; a layer's four
    # projections, its router and three matrices a held expert
    want_fills = 2 + cfg.n_layers * (4 + 1 + 3 * cfg.held)
    assert ran.get("materialize.fill_fastpath_hits") == want_fills
    assert ran.get("materialize.torch_fallback_params", 0) == 0
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, smallthinker.abstract_params(cfg)
    )
    assert params["dense_layers"] == {}
    assert (np.asarray(params["moe_layers"]["mlp_norm"]) == 1).all()
    assert np.asarray(params["moe_layers"]["router"]).std() > 0.01


def test_scopes_and_counters():
    """The names a trace is read by: ``moe/router`` FIRST in a layer,
    ``attn`` with ``norm``, ``proj_in``, ``rope``, ``proj_out`` and the
    kernels under it (banded in the window layers, plain in the full
    ones), ``moe/dispatch|experts|combine``, ``stack``; the counters of the
    share, the unit, the router's input and the window."""
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    params = jax.eval_shape(
        lambda: smallthinker.init_params(jax.random.PRNGKey(0), cfg)
    )
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    text = jax.jit(jax.grad(
        lambda p, t: smallthinker.loss_fn(p, t, t, cfg, attn_impl="pallas")[0]
    )).lower(params, tok).as_text(debug_info=True)
    c1, h1 = telemetry.counters(), telemetry.histograms()
    assert "(stack)/" in text
    for scope in ("attn/norm", "attn/proj_in", "attn/rope",
                  "attn/proj_out", "moe/router", "moe/dispatch", "moe/experts",
                  "moe/combine"):
        assert f"{scope}/" in text, scope
    assert "(embed)/" in text and "(head)/" in text  # outside every block
    assert "attn/flash_win_fwd/" in text and "flash_win_bwd_fused/" in text
    assert "attn/flash_fwd/" in text and "flash_bwd_fused/" in text
    # program order of a layer: its router before its attention
    layer = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), params["moe_layers"]
    )
    x = jax.ShapeDtypeStruct((1, 128, cfg.dim), jnp.float32)
    block = smallthinker._build_block(
        dataclasses.replace(cfg, remat=False), attn_impl="jnp"
    )(FULL)
    stacks = [
        str(eqn.source_info.name_stack)
        for eqn in jax.make_jaxpr(block)(x, layer).eqns
    ]
    first = {
        scope: next(i for i, s in enumerate(stacks) if s.startswith(scope))
        for scope in ("moe/router", "attn", "moe/dispatch")
    }
    assert first["moe/router"] < first["attn"] < first["moe/dispatch"]

    def rose(name):
        return c1.get(name, 0) - c0.get(name, 0)

    held, total = rose("moe.experts_held"), rose("moe.experts_total")
    assert held > 0 and total == 2 * held
    assert rose("moe.unit{kind=relu}") > 0 and not rose("moe.unit{kind=silu}")
    assert rose("moe.router_input{from=layer_input}") == rose("moe.unit{kind=relu}")
    assert not rose("moe.router_input{from=expert_input}")
    # one scanned body of four layers: one full, three banded
    assert rose("attention.flash_window{window=48}") == 3
    assert rose("attention.flash{interpret=true}") == 4
    hist = "attention.window_kv_blocks"
    assert h1[hist]["count"] - h0.get(hist, {}).get("count", 0) >= 3


def test_train_step_takes_the_family():
    """``make_train_step`` takes ``models.smallthinker`` as it takes the
    other routed families (``LOSS_HAS_AUX``), empty ``dense_layers`` and
    all: the loss falls, the counts come out, the share's stacks keep
    their shapes."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(
        smallthinker.smallthinker_test(), n_experts_held=4, first_expert_held=2
    )
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=smallthinker, attn_impl="jnp"
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
    n = tokens.size * cfg.experts_per_token * cfg.n_layers
    assert 0 < float(metrics["moe"]["local_assignments"]) < n
    assert state.params["moe_layers"]["e_gate"].shape == (
        8, 4, cfg.dim, cfg.expert_dim
    )
    assert state.params["dense_layers"] == {}
