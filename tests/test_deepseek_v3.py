"""DeepSeek-V3 family (latent attention, routed experts told which they
hold): the benchmark's plain reference against ``transformers``, the family
against the reference, the share against the whole, dropless routing, and
the paper's path with the absent experts dropped before materialization.

CPU, float32, seeded: values and counts only.
"""

import dataclasses
import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import convert, deepseek_v3 as ds, llama as llama_mod
from torchdistx_tpu.ops import routed_experts as routed_mod
from torchdistx_tpu.ops.routed_experts import routed_experts

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from families import deepseek_v3 as family  # noqa: E402
from reference import common, deepseek_v3 as ref  # noqa: E402

CONFIG = "kanana-2-30b-a3b-instruct-2601"


def _sizes(**over):
    """The configuration file's ``tiny`` block over its published keys."""
    with open(os.path.join(BENCH, "configs", f"{CONFIG}.json")) as f:
        c = json.load(f)
    c.update(c.pop("tiny"))
    c.update(over)
    return c


def _ref_loss(params, tokens, targets, sizes):
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        return common.cross_entropy(ref.head(params, x, jnp.float32), targets)


def _tokens(sizes, shape=(2, 48), seed=1):
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], size=(shape[0], shape[1] + 1)
    )
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


@pytest.fixture(scope="module")
def share():
    """Sizes, native config and seeded parameters of a share: experts 2-5
    of 8, selection bias not zero."""
    sizes = _sizes()
    mod, cfg = family.native(sizes, jnp.float32)
    assert (cfg.held, cfg.first_expert_held, cfg.n_experts) == (4, 2, 8)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    params["moe_layers"]["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(9), params["moe_layers"]["router_bias"].shape
    )
    return sizes, cfg, params


def test_reference_matches_transformers():
    """(a) every expert held: the plain reference's logits are those of
    ``DeepseekV3ForCausalLM`` (eager attention) on the same weights."""
    import torch

    sizes = _sizes(n_routed_experts=8, first_expert_held=0)
    build, hf_config = family.hf(sizes)
    hf_config._attn_implementation = "eager"
    torch.manual_seed(0)
    module = build(hf_config).eval()
    for layer in module.model.layers[1:]:
        layer.mlp.gate.e_score_correction_bias.normal_(0.0, 0.05)
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {
        k: v.detach().numpy()
        for k, v in {
            **dict(module.named_parameters()), **dict(module.named_buffers())
        }.items()
    }
    params = convert.deepseek_v3_params_from_hf(arrays, cfg)
    tokens, _ = _tokens(sizes)
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).logits.numpy()
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        got = ref.head(params, x, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=0)
    # ... and so are the native family's, every expert held
    np.testing.assert_allclose(
        np.asarray(ds.forward(params, tokens, cfg, attn_impl="jnp")), want,
        atol=1e-4, rtol=0,
    )


@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(share, impl, remat):
    """(b) a held subset: loss and every gradient, through jnp attention
    and the interpreted flash kernels, with and without remat."""
    sizes, cfg, params = share
    cfg = dataclasses.replace(cfg, remat=remat)
    tokens, targets = _tokens(sizes)
    (loss, aux), grads = jax.value_and_grad(
        lambda p: ds.loss_fn(p, tokens, targets, cfg, attn_impl=impl),
        has_aux=True,
    )(params)
    want, want_grads = jax.value_and_grad(_ref_loss)(
        params, tokens, targets, sizes
    )
    assert abs(float(loss) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        scale = float(jnp.abs(w).max()) + 1e-8
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path
    # the selection bias is a buffer: no gradient moves it
    assert not np.asarray(grads["moe_layers"]["router_bias"]).any()
    n = tokens.size * cfg.experts_per_token * cfg.n_moe_layers
    assert 0 < float(aux["moe"]["local_assignments"]) < n
    assert float(aux["moe"]["load_max_over_mean"]) >= 1.0


def test_four_shares_add_up_to_the_whole_layer():
    """(c) 32 experts in four shares of 8: the routed parts summed, the
    shared expert counted once, are the uncut reference's layer output."""
    sizes = _sizes(
        n_routed_experts=32, n_routed_experts_total=32, first_expert_held=0,
        num_experts_per_tok=6,
    )
    _, cfg = family.native(sizes, jnp.float32)
    cfg = dataclasses.replace(cfg, n_dense_layers=0, n_moe_layers=1)
    params = ds.init_params(jax.random.PRNGKey(2), cfg)
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
    assert assigned == h.shape[0] * h.shape[1] * 6  # every choice, once


@pytest.mark.parametrize("gates", ["softmax", "sigmoid"])
@pytest.mark.parametrize("case", ["one_expert", "one_held_one_absent", "all_absent"])
def test_dropless_under_imbalance(gates, case):
    """(e) a router that sends EVERY token to the same experts loses
    nothing (the capacity cases of tests/test_moe.py, which drop, have no
    counterpart here): the output is the dense sum over the held choices,
    for every token."""
    t, d, f, e = 96, 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    h = jax.random.normal(ks[0], (t, d))
    eg, eu = (0.3 * jax.random.normal(k, (4, d, f)) for k in ks[1:3])
    ed = 0.3 * jax.random.normal(ks[3], (4, f, d))
    first = 2  # held: experts 2..5
    picked = {"one_expert": (3, 4), "one_held_one_absent": (5, 7),
              "all_absent": (0, 6)}[case]
    router = 0.01 * jax.random.normal(ks[4], (d, e))
    bias = jnp.zeros(e).at[jnp.asarray(picked)].set(50.0)
    if gates == "softmax":  # no selection bias: tilt the logits themselves
        h = h.at[:, 0].set(40.0)
        router = router.at[0].set(bias / 40.0)
        bias = None
    out, stats = routed_experts(
        h, router, eg, eu, ed, top_k=2, gates=gates, bias=bias, scale=1.5,
        first_held=first,
    )
    assert (np.sort(np.asarray(stats["selected"]), -1) == np.asarray(picked)).all()
    logits = h @ router
    s = jax.nn.softmax(logits, -1) if gates == "softmax" else jax.nn.sigmoid(logits)
    w = s[:, list(picked)]
    w = 1.5 * w / w.sum(-1, keepdims=True)
    want = jnp.zeros_like(h)
    for j, ex in enumerate(picked):
        if first <= ex < first + 4:
            i = ex - first
            want += w[:, j:j + 1] * (
                (jax.nn.silu(h @ eg[i]) * (h @ eu[i])) @ ed[i]
            )
    np.testing.assert_allclose(out, want, atol=1e-5, rtol=1e-4)
    held = [ex for ex in picked if first <= ex < first + 4]
    sizes = np.zeros(4, int)
    sizes[[ex - first for ex in held]] = t
    assert (np.asarray(stats["group_sizes"]) == sizes).all()
    assert float(stats["local_assignments"]) == t * len(held)
    if held:
        assert (np.abs(np.asarray(out)).max(-1) > 0).all()  # no token lost
    else:
        assert not np.asarray(out).any()


def _dense_held_sum(h, router, eg, eu, ed, *, top_k, bias, scale, first):
    """The layer with no sort and no chunk: every held expert's FFN over
    EVERY token, weighted by what the (sigmoid) router gave that token's
    choice of it, 0 where it was not chosen."""
    scores = jax.nn.sigmoid(h @ router)
    _, selected = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, selected, axis=-1)
    w = scale * w / w.sum(-1, keepdims=True)
    out = jnp.zeros_like(h)
    for i in range(eg.shape[0]):
        wi = jnp.where(selected == first + i, w, 0.0).sum(-1, keepdims=True)
        out += wi * ds._swiglu(h, eg[i], eu[i], ed[i])
    return out


# name: (top_k, experts every token is sent to (None: the router's own
# choice), the row bound R forced, the row tile forced (an overflow chunk
# holds R2 = an eighth of R up to the tile, at most R), held assignments M,
# chunks = the first + ceil((M - R) / R2))
CHUNK_CASES = {
    "no_chunk_all_absent": (2, (0, 6), 64, 512, 0, 0),
    "one_chunk": (2, None, 128, 512, 79, 1),
    "two_chunks": (2, None, 64, 512, 79, 2),  # R2 = R
    "overflow_in_eighths": (2, None, 64, 8, 79, 3),  # R2 = 8: 64 + 8 + 7
    "overflow_of_one_row": (2, None, 78, 2, 79, 2),  # R2 = 10
    "every_token_to_one_held_expert": (1, (3,), 32, 512, 96, 3),  # T*k / R
    "every_token_to_one_held_expert_in_eighths": (1, (3,), 32, 4, 96, 17),
}


@pytest.mark.parametrize("case", CHUNK_CASES)
def test_row_chunks_cover_every_held_row(case, monkeypatch):
    """The sorted side runs a chunk of ``R`` rows and ``ceil((M - R) /
    R2)`` of ``R2`` rows (which add their rows to their tokens, where the
    first gathers every token's), counted
    from the routing, and whatever the count the layer and its gradients
    (h, router, the three expert matrices, and the routing weights
    themselves through a scale a token) are the dense per-token sum over
    the held choices; ``stats["row_chunks"]`` says how many ran."""
    top_k, picked, bound, tile, m, chunks = CHUNK_CASES[case]
    t, d, f, e, first = 96, 16, 8, 8, 2  # held: experts 2..5
    ks = jax.random.split(jax.random.PRNGKey(6), 6)
    h = jax.random.normal(ks[0], (t, d))
    router = 0.3 * jax.random.normal(ks[1], (d, e))
    eg, eu = (0.3 * jax.random.normal(k, (4, d, f)) for k in ks[2:4])
    ed = 0.3 * jax.random.normal(ks[4], (4, f, d))
    cot = jax.random.normal(ks[5], (t, d))
    bias = 0.05 * jnp.arange(e, dtype=jnp.float32)
    if picked is not None:
        bias = bias.at[jnp.asarray(picked)].set(50.0)
    monkeypatch.setattr(routed_mod, "_row_bound", lambda *shape: bound)
    monkeypatch.setattr(routed_mod, "_ROW_TILE", tile)
    tail = routed_mod._tail_rows(bound)
    kw = dict(top_k=top_k, bias=bias)

    # ``scale`` a token: its gradient is the routing weights' own, summed
    # over a token's choices, with no router behind it.
    def layer(*args, scale):
        out, stats = routed_experts(
            *args, gates="sigmoid", first_held=first, scale=scale, **kw
        )
        return (out * cot).sum(), (out, stats)

    def dense(*args, scale):
        out = _dense_held_sum(*args, first=first, scale=scale, **kw)
        return (out * cot).sum(), out

    def grad(fn):
        return jax.grad(
            lambda *args: fn(*args[:-1], scale=args[-1]),
            argnums=(0, 1, 2, 3, 4, 5), has_aux=True,
        )

    args = (h, router, eg, eu, ed, jnp.full((t, 1), 1.5))
    grads, (out, stats) = jax.jit(grad(layer))(*args)
    want_grads, want = grad(dense)(*args)
    assert int(stats["local_assignments"]) == m
    assert int(stats["row_chunks"]) == chunks
    assert chunks == min(m, 1) + -(-max(m - bound, 0) // tail)
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)
    for name, g, g_want in zip(
        ("h", "router", "e_gate", "e_up", "e_down", "routing weights"),
        grads, want_grads,
    ):
        np.testing.assert_allclose(g, g_want, atol=2e-5, rtol=1e-5, err_msg=name)
    if not m:
        assert not any(np.asarray(g).any() for g in grads)


@pytest.mark.parametrize("unit", ["silu", "relu"])
def test_a_chunks_backward_is_the_vjp_of_its_three_products(unit):
    """bfloat16, either gated unit: the written-out backward of a chunk (six grouped
    products from the kept gate and up products, the routing weights'
    gradient from the UNWEIGHTED transposed product) against ``jax.vjp``
    of the three forward products on the same rows, the form the layer
    had; within 4 ulp of bfloat16 of each result's largest entry (the two
    round ``g * w`` at different places), rows outside every group apart."""
    r, d, f, n = 96, 64, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(11), 6)
    bf = jnp.bfloat16
    xs = jax.random.normal(ks[0], (r, d)).astype(bf)
    eg, eu = (
        (0.3 * jax.random.normal(k, (n, d, f))).astype(bf) for k in ks[1:3]
    )
    ed = (0.3 * jax.random.normal(ks[3], (n, f, d))).astype(bf)
    g = jax.random.normal(ks[4], (r, d))
    w_rows = jax.random.uniform(ks[5], (r,), minval=0.1, maxval=1.0)
    sizes = jnp.asarray([40, 0, 17, 23], jnp.int32)  # 80 of 96 rows held
    m = int(sizes.sum())

    def experts(xs, eg, eu, ed):
        return routed_mod._down(
            *routed_mod._gate_up(xs, eg, eu, sizes), ed, sizes, unit
        )

    y, pull = jax.vjp(experts, xs, eg, eu, ed)
    want_dxs, *want_experts = pull((g * w_rows[:, None]).astype(bf))
    want_dw = (y.astype(jnp.float32) * g).sum(-1)

    gate, up = routed_mod._gate_up(xs, eg, eu, sizes)
    dxs, d_experts, dw = jax.jit(
        functools.partial(routed_mod._chunk_bwd, unit=unit)
    )(xs, gate, up, eg, eu, ed, sizes, g, w_rows)
    assert dxs.dtype == bf and dw.dtype == jnp.float32
    assert all(a.dtype == bf for a in d_experts)
    for name, got, want in zip(
        ("xs", "e_gate", "e_up", "e_down", "routing weights"),
        (dxs[:m], *d_experts, dw[:m]), (want_dxs[:m], *want_experts, want_dw[:m]),
    ):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert np.abs(want).max() > 0.1, name
        assert np.abs(got - want).max() <= 2.0 ** -6 * np.abs(want).max(), name


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("t", [96, 1200])  # one token block, and three
def test_an_overflow_chunk_adds_its_rows_to_their_tokens(t, dtype):
    """``_add_rows``: the grouped one-hot product over the rows sorted by
    token against a scatter-add, with tokens that own several rows of the
    chunk, rows no held expert works on (left out, whatever they hold) and
    a last token block that is not whole; the sums are float32 of the
    rows' own dtype, so they are exact in both."""
    r, d = 160, 24
    ks = jax.random.split(jax.random.PRNGKey(t), 4)
    rows = jax.random.normal(ks[0], (r, d)).astype(dtype)
    at = jax.random.randint(ks[1], (r,), 0, t).at[:40].set(t - 1)
    at = at.at[40:48].set(0)
    held = jax.random.uniform(ks[2], (r,)) < 0.8
    rows = jnp.where(held[:, None], rows, jnp.nan)  # never read
    out = jax.random.normal(ks[3], (t, d))
    got = jax.jit(routed_mod._add_rows)(out, at, rows, held)
    want = out.at[at].add(
        jnp.where(held[:, None], rows.astype(jnp.float32), 0.0)
    )
    assert got.dtype == jnp.float32 and got.shape == out.shape
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=0)


def _avals(jaxpr):
    """Every array a jaxpr computes, the bodies of its loops, calls and
    custom rules included."""
    for eqn in jaxpr.eqns:
        yield from (v.aval for v in eqn.outvars)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _avals(sub)


@pytest.mark.parametrize(
    "bound,tile,chunks", [(128, 512, 1), (32, 512, 3), (64, 4, 5)]
)
def test_relu_unit_and_a_router_input_apart_from_the_experts(
    bound, tile, chunks, monkeypatch
):
    """float32, one chunk and several: the ``relu`` unit through the
    hand-written backward, the tokens routed by ANOTHER tensor ``x`` than
    the experts read (``route`` then ``routing=``), softmax over the six
    selected logits; the layer and its gradients (``x``, ``h``, router,
    the three expert matrices) against ``jax.grad`` of the plain layer."""
    t, d, f, e, first, top_k = 96, 16, 8, 8, 2, 2  # held: experts 2..5
    ks = jax.random.split(jax.random.PRNGKey(8), 7)
    x, h, cot = (jax.random.normal(k, (t, d)) for k in ks[:3])
    router = 0.3 * jax.random.normal(ks[3], (d, e))
    eg, eu = (0.3 * jax.random.normal(k, (4, d, f)) for k in ks[4:6])
    ed = 0.3 * jax.random.normal(ks[6], (4, f, d))
    monkeypatch.setattr(routed_mod, "_row_bound", lambda *shape: bound)
    monkeypatch.setattr(routed_mod, "_ROW_TILE", tile)

    def layer(x, h, router, eg, eu, ed):
        out, stats = routed_experts(
            h, router, eg, eu, ed, top_k=top_k, first_held=first,
            unit="relu", routing=routed_mod.route(x, router, top_k=top_k),
        )
        return (out * cot).sum(), (out, stats)

    def plain(x, h, router, eg, eu, ed):
        top, selected = jax.lax.top_k(x @ router, top_k)
        w = jax.nn.softmax(top, axis=-1)  # over the SELECTED logits
        out = jnp.zeros_like(h)
        for i in range(eg.shape[0]):
            wi = jnp.where(selected == first + i, w, 0.0).sum(-1, keepdims=True)
            out += wi * ((jax.nn.relu(h @ eg[i]) * (h @ eu[i])) @ ed[i])
        return (out * cot).sum(), out

    args = (x, h, router, eg, eu, ed)
    c0 = telemetry.counters()
    grads, (out, stats) = jax.jit(
        jax.grad(layer, argnums=tuple(range(6)), has_aux=True)
    )(*args)
    c1 = telemetry.counters()
    want_grads, want = jax.grad(plain, argnums=tuple(range(6)), has_aux=True)(*args)
    assert int(stats["row_chunks"]) == chunks
    np.testing.assert_allclose(out, want, atol=2e-5, rtol=0)
    for name, g, g_want in zip(
        ("x", "h", "router", "e_gate", "e_up", "e_down"), grads, want_grads
    ):
        assert np.abs(np.asarray(g_want)).max() > 1e-3, name
        np.testing.assert_allclose(g, g_want, atol=2e-5, rtol=1e-5, err_msg=name)
    for name in ("moe.unit{kind=relu}", "moe.router_input{from=layer_input}"):
        assert c1.get(name, 0) - c0.get(name, 0) == 1, name
    with pytest.raises(ValueError, match="unknown unit"):
        routed_experts(h, router, eg, eu, ed, top_k=top_k, unit="gelu")


@pytest.mark.parametrize("program", ["layer", "gradient"])
def test_no_worst_case_buffer_when_a_share_is_held(program):
    """With ``Eh < E`` nothing on the sorted side is ``T*k`` rows long:
    no array of the layer, nor of its gradient, holds ``T*k`` rows of an
    expert's width ``F`` or more (the routing's integer vectors and the
    ``(T*k, Eh)`` comparison that counts the groups are narrower)."""
    t, d, f, e, n_held, k = 256, 32, 16, 16, 4, 4
    shapes = [(t, d), (d, e), (n_held, d, f), (n_held, d, f), (n_held, f, d)]
    bound = routed_mod._row_bound(t * k, n_held, e)
    assert bound < t * k

    def layer(*args):
        return routed_experts(*args, top_k=k, gates="sigmoid")[0].sum()

    fn = layer if program == "layer" else jax.grad(layer, argnums=(0, 1, 2, 3, 4))
    jaxpr = jax.make_jaxpr(fn)(
        *(jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes)
    )
    avals = [a for a in _avals(jaxpr.jaxpr) if getattr(a, "shape", ())]
    assert any(a.shape == (bound, f) for a in avals)
    wide = [
        a for a in avals
        if np.prod(a.shape[:-1]) == t * k and a.shape[-1] >= f
    ]
    assert not wide, wide


def _rope_gathered(x, cos, sin):
    """The oracle: the form the family had before the rotation was done in
    place.  The pairs' first members gathered into the first half, the
    second into the other, then llama's half-split rotation."""
    pairs = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    x = jnp.concatenate([pairs[..., 0], pairs[..., 1]], axis=-1)
    return llama_mod._rope_apply(x, cos, sin)


def _interleaved(x):
    """``[first members | second members]`` -> the pairs where they lay."""
    half = x.shape[-1] // 2
    return jnp.stack([x[..., :half], x[..., half:]], axis=-1).reshape(x.shape)


def _qkv_gathered(x, lp, cfg):
    """The oracle: q, k and v as ``_attn`` assembled them before, by slices
    and concatenations of the activations."""
    b, s, _ = x.shape
    nope, rope, H = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.n_heads
    h = llama_mod._rmsnorm(x, lp["attn_norm"], cfg.norm_eps)
    q = (h @ lp["wq"]).reshape(b, s, H, cfg.qk_dim)
    kva = h @ lp["wkv_a"]
    c = llama_mod._rmsnorm(kva[..., : cfg.kv_rank], lp["kv_norm"], cfg.norm_eps)
    kv = (c @ lp["wkv_b"]).reshape(b, s, H, nope + cfg.v_dim)
    cos, sin = llama_mod._rope_tables(
        jnp.arange(s)[None], cfg.rope_theta, rope // 2, x.dtype
    )
    q_rope = _rope_gathered(q[..., nope:], cos, sin)
    k_rope = _rope_gathered(kva[..., cfg.kv_rank:][:, :, None, :], cos, sin)
    q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_rope, (b, s, H, rope))], axis=-1
    )
    return q, k, kv[..., nope:]


both_dtypes = pytest.mark.parametrize(
    "dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"]
)


def _same_bits(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape and a.tobytes() == b.tobytes()


@both_dtypes
def test_rope_in_place_is_the_gathered_rotation_bit_for_bit(dtype):
    """q over its heads and the one-head k: every element the in-place
    rotation gives is the gathered form's, in interleaved order; q k^T of
    the two forms differs by the order of a float32 sum."""
    rope, s = 64, 64
    kq, kk = jax.random.split(jax.random.PRNGKey(3))
    q = jax.random.normal(kq, (2, s, 4, rope), jnp.float32).astype(dtype)
    k = jax.random.normal(kk, (2, s, 1, rope), jnp.float32).astype(dtype)
    cos, sin = llama_mod._rope_tables(jnp.arange(s)[None], 1e4, rope // 2, dtype)
    twice = [jnp.repeat(t, 2, axis=-1) for t in (cos, sin)]
    swap = ds._pair_swap(rope, dtype)
    got_q = ds._rope_in_place(q, *twice, swap)
    got_k = ds._rope_in_place(k, *twice, swap)
    want_q, want_k = _rope_gathered(q, cos, sin), _rope_gathered(k, cos, sin)
    assert _same_bits(got_q, _interleaved(want_q))
    assert _same_bits(got_k, _interleaved(want_k))
    assert not _same_bits(got_q, q)

    def scores(q, k):
        return jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32),
            jnp.broadcast_to(k, q.shape).astype(jnp.float32),
            precision="highest",
        )

    np.testing.assert_allclose(
        np.asarray(scores(got_q, got_k)), np.asarray(scores(want_q, want_k)),
        atol=2e-5, rtol=0,
    )


@both_dtypes
def test_attn_hands_the_kernels_the_q_k_v_it_assembled_by_slices(
    dtype, monkeypatch
):
    """What ``_attn`` passes to ``attention`` against the old assembly on
    the same layer: q's no-rope columns pass the whole-width rotation
    unchanged, the rope columns of q and k are the gathered form's,
    re-interleaved, and k's no-rope half and v are the joint product's."""
    cfg = dataclasses.replace(ds.deepseek_v3_test(), dtype=dtype)
    nope = cfg.qk_nope_dim
    params = ds.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda a: a[1], params["moe_layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 48, cfg.dim), jnp.float32)
    x = x.astype(dtype)
    seen = {}

    def attention(q, k, v, **kw):
        seen.update(q=q, k=k, v=v)
        return jnp.zeros(v.shape, v.dtype)

    monkeypatch.setattr(ds, "attention", attention)
    ds._attn(x, lp, cfg, mesh=None, attn_impl="jnp")
    q, k, v = _qkv_gathered(x, lp, cfg)
    assert _same_bits(seen["q"][..., :nope], q[..., :nope])
    assert _same_bits(seen["q"][..., nope:], _interleaved(q[..., nope:]))
    assert _same_bits(seen["k"][..., nope:], _interleaved(k[..., nope:]))
    # The CPU's matrix product sums a column in another order when the
    # weight has other columns beside it (the chip's does not): an ulp.
    for got, want in ((seen["k"][..., :nope], k[..., :nope]), (seen["v"], v)):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        assert got.shape == want.shape
        ulp = float(jnp.finfo(dtype).eps) * np.abs(want).max()
        assert np.abs(got - want).max() <= 2 * ulp


@both_dtypes
def test_latent_cut_in_the_weights_gives_the_joint_products_halves(dtype):
    """``c @ wkv_b`` with the weight cut at each head's column ``nope``
    against the joint product cut in the activation, bit for bit: small
    whole numbers, so every sum is exact in any order and only a wrong
    column could differ."""
    b, s, rank, heads, nope, v_dim = 2, 24, 16, 4, 24, 16
    kc, kw = jax.random.split(jax.random.PRNGKey(5))
    c = jax.random.randint(kc, (b, s, rank), -3, 4).astype(dtype)
    w = jax.random.randint(kw, (rank, heads * (nope + v_dim)), -3, 4).astype(dtype)
    kv = (c @ w).reshape(b, s, heads, nope + v_dim)
    assert float(jnp.abs(kv.astype(jnp.float32)).max()) <= 256  # exact in bf16
    k_nope, v = ds._latent_up(c, w, heads, nope)
    assert _same_bits(k_nope, kv[..., :nope])
    assert _same_bits(v, kv[..., nope:])
    assert len(np.unique(np.asarray(kv, np.float32))) > 16


def test_no_activation_is_cut_or_gathered():
    """Structure: neither the loss nor its gradient holds an array whose
    minor dimension is a pair (the interleave gather made ``(..., rope/2,
    2)``) or the joint ``kv (B, S, H, nope + v_dim)`` activation that was
    sliced twice."""
    cfg = ds.deepseek_v3_test()
    params = ds.abstract_params(cfg)
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(
            lambda p, t: ds.loss_fn(p, t, t, cfg, attn_impl="jnp")[0]
        )
    )(params, tok)
    shapes = {a.shape for a in _avals(jaxpr.jaxpr) if getattr(a, "shape", ())}
    assert (2, 32, cfg.n_heads, cfg.qk_dim) in shapes  # q and k are there
    pairs = [s for s in shapes if s[-2:] == (cfg.qk_rope_dim // 2, 2)]
    assert not pairs, pairs
    assert (2, 32, cfg.n_heads, cfg.qk_nope_dim + cfg.v_dim) not in shapes
    assert (2, 32, cfg.n_heads * (cfg.qk_nope_dim + cfg.v_dim)) not in shapes


def test_absent_experts_are_never_materialized():
    """(f) the paper's path: the layer is constructed with every expert,
    fake; the absent ones are dropped; materialization fills the share's
    parameters and no more."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _sizes()
    build, hf_config = family.hf(sizes)
    module = di.deferred_init(build, hf_config)
    _, cfg = family.native(sizes, jnp.float32)
    assert sum(p.numel() for p in module.parameters()) == ds.num_params(cfg) - (
        cfg.n_moe_layers * cfg.n_experts  # the bias is a buffer there
    )
    full = dataclasses.replace(cfg, n_experts_held=None)
    assert ds.num_params(full) - ds.num_params(cfg) == (
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
    n_leaves = len(
        jax.tree.leaves(ds.abstract_params(cfg))
    ) - 3 * cfg.n_moe_layers  # e_gate/e_up/e_down are stacks of leaves
    per_layer = 7
    want_fills = (
        3  # embed, final norm, head
        + cfg.n_layers * per_layer + cfg.n_dense_layers * 3
        + cfg.n_moe_layers * (1 + 3 + 3 * cfg.held)
    )
    # every fill that ran is a held parameter's (the buffers are not fills)
    assert ran.get("materialize.fill_fastpath_hits") == want_fills
    assert ran.get("materialize.torch_fallback_params", 0) == 0
    assert n_leaves > 0
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, ds.abstract_params(cfg)
    )


def test_scopes_and_counters(monkeypatch):
    """The names a trace is read by: ``attn`` with the kernels under it,
    ``mlp``, ``moe/router|dispatch|experts|combine|shared``; the host
    counters of the share and of the rotation's form."""
    sizes, (_, cfg) = _sizes(), family.native(_sizes(), jnp.float32)
    params = jax.eval_shape(lambda: ds.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 32), jnp.int32)
    traced, attn = [], ds._attn
    monkeypatch.setattr(
        ds, "_attn", lambda *a, **kw: traced.append(1) or attn(*a, **kw)
    )
    c0 = telemetry.counters()
    text = jax.jit(
        jax.grad(lambda p, t: ds.loss_fn(p, t, t, cfg, attn_impl="pallas")[0])
    ).lower(params, tok).as_text(debug_info=True)
    c1 = telemetry.counters()
    for scope in ("attn", "mlp", "moe/router", "moe/dispatch", "moe/experts",
                  "moe/combine", "moe/shared", "attn/proj_in", "attn/rope",
                  "attn/concat"):
        assert f"{scope}/" in text, scope
    # the form of the rotation a program holds: one count a traced ``_attn``
    in_place = "attn.rope{form=in_place}"
    assert c1[in_place] - c0.get(in_place, 0) == len(traced) == 2
    assert "attn/flash_fwd/" in text and "flash_bwd_fused/" in text
    held = c1["moe.experts_held"] - c0.get("moe.experts_held", 0)
    total = c1["moe.experts_total"] - c0.get("moe.experts_total", 0)
    assert held > 0 and total == 2 * held
    assert sizes["n_routed_experts_total"] == 2 * sizes["n_routed_experts"]
    # one trace of the routed layer's backward rule: the scanned block's
    kept = "moe.first_chunk{forward=kept}"
    assert c1[kept] - c0.get(kept, 0) == 1


def test_train_step_carries_the_counts_out_and_fit_records_them():
    """``make_train_step`` differentiates a ``LOSS_HAS_AUX`` family with
    ``has_aux`` and hands ``metrics["moe"]`` on; ``fit`` reads it into the
    histograms ``moe.local_assignments``, ``moe.load_max_over_mean`` and
    ``moe.row_chunks``."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.fit import fit
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = dataclasses.replace(
        ds.deepseek_v3_test(), n_experts_held=4, first_expert_held=2
    )
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=ds, attn_impl="jnp"
    )
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
        ts.batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    before = {
        k: telemetry.histograms().get(k, {}).get("count", 0)
        for k in (
            "moe.local_assignments", "moe.load_max_over_mean", "moe.row_chunks"
        )
    }
    seen = []
    state, metrics = fit(
        init_fn, step_fn, [batch] * 5, key=jax.random.PRNGKey(0), n_steps=5,
        handle_preemption=False,
        on_metrics=lambda step, m: seen.append(float(m["loss"])),
    )
    assert seen[-1] < seen[0] and np.isfinite(seen).all()
    assert set(metrics["moe"]) == {
        "local_assignments", "load_max_over_mean", "row_chunks"
    }
    # Half the experts are held, so a chunk is two thirds of the T*k rows:
    # every layer runs one or two, and the step carries their sum out.
    assert cfg.n_moe_layers <= int(metrics["moe"]["row_chunks"]) <= 2 * cfg.n_moe_layers
    n = tokens.size * cfg.experts_per_token * cfg.n_moe_layers
    assert 0 < float(metrics["moe"]["local_assignments"]) < n
    after = telemetry.histograms()
    for k, n0 in before.items():
        assert after[k]["count"] == n0 + 5, k
    assert after["moe.load_max_over_mean"]["min"] >= 1.0
    assert state.params["moe_layers"]["e_gate"].shape[:2] == (2, 4)
