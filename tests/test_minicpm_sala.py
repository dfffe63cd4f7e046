"""MiniCPM-SALA family (block-sparse attention that picks its own blocks per
token, decayed linear (lightning) attention, 1 to 3 by layer, muP
scalings): the benchmark's plain reference against the published
architecture in torch, the family against the reference on the sparse
path (above ``dense_len``) and the dense one, through ``jnp`` and the
interpreted kernels, with and without remat; the paper's path
(``deferred_init`` -> ``materialize_module_jax`` -> ``convert``); the
program's choice of blocks against the reference's; the train step; the
scopes and counters.

CPU, float32, seeded: values and counts only.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import convert, minicpm_sala
from torchdistx_tpu.ops import sparse_attention as sa

import _families  # puts benchmarks/ on sys.path
from families import minicpm_sala as family
from reference import common
from reference import minicpm_sala as ref

CONFIG = "minicpm-sala"
SHAPE = (2, 128)
SPARSE, LIGHTNING = minicpm_sala.SPARSE, minicpm_sala.LIGHTNING


def _seeded(cfg, seed=0):
    """Seeded parameters with norms off one, so that no norm's weight can
    drop out unseen, and the layers' matrices eight times as large: 0.02 is
    drawn for a width of 4,096, and at the test's 64 the blocks would add
    next to nothing to a stream of rows 12 wide."""
    params = minicpm_sala.init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 9), 64))
    for stack in ("sparse_layers", "lightning_layers"):
        for name, a in params[stack].items():
            if name.endswith("norm"):
                params[stack][name] = a + 0.2 * jax.random.normal(next(keys), a.shape)
            else:
                params[stack][name] = 8 * a
    return params


@pytest.fixture(scope="module")
def tiny():
    sizes = _families.sizes(CONFIG)
    _, cfg = family.native(sizes, jnp.float32)
    assert cfg.mixer_types == (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING) * 2
    assert cfg.n_heads // cfg.n_kv_heads == 2
    return sizes, cfg, _seeded(cfg)


@pytest.fixture(scope="module")
def reference(tiny):
    """The plain reference's loss and gradients at the tiny parameters,
    evaluated once a shape of tokens."""
    sizes, _, params = tiny
    return _families.reference_at(ref, sizes, params)


@pytest.mark.parametrize("seq", [32, 128])
def test_reference_matches_the_torch_module(seq):
    """The plain reference's logits are those of the published architecture
    in torch on the same weights, through ``convert``, and so are the
    family's: at 128 positions the sparse layers choose (6 of 16 blocks of
    8), at 32 (``dense_len``) they are dense."""
    import torch

    sizes = _families.sizes(CONFIG)
    build, torch_config = family.hf(sizes)
    torch.manual_seed(0)
    module = build(torch_config).eval()
    with torch.no_grad():
        for name, p in module.named_parameters():
            if "norm" in name or "layernorm" in name:
                p.add_(0.2 * torch.randn_like(p))
            elif "embed" not in name:
                p.mul_(8)
    _, cfg = family.native(sizes, jnp.float32)
    arrays = {k: v.detach().numpy() for k, v in module.state_dict().items()}
    params = convert.minicpm_sala_params_from_hf(arrays, cfg)
    tokens, _ = _families.tokens(sizes, (2, seq))
    with torch.no_grad():
        want = module(torch.tensor(np.asarray(tokens))).numpy()
    with common.precision(jnp.float32):
        got = ref.head(params, ref.hidden(params, tokens, sizes, jnp.float32), jnp.float32)
    np.testing.assert_allclose(np.asarray(got), want, atol=2e-5, rtol=0)
    for impl in ("jnp", "pallas"):
        np.testing.assert_allclose(
            np.asarray(minicpm_sala.forward(params, tokens, cfg, attn_impl=impl)),
            want, atol=2e-5, rtol=0,
        )


@pytest.mark.parametrize("seq", [32, 128])
@pytest.mark.parametrize("remat", [False, True])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_loss_and_gradients_match_the_reference(
    tiny, reference, impl, remat, seq
):
    """Loss and every gradient, on the sparse path (128 > ``dense_len``)
    and the dense one (32), through the jnp paths and the interpreted
    kernels, with and without remat; two periods under the period scan."""
    sizes, cfg, params = tiny
    cfg = dataclasses.replace(cfg, remat=remat)
    tokens, targets = _families.tokens(sizes, (2, seq))
    (loss, aux), grads = jax.value_and_grad(
        lambda p: minicpm_sala.loss_fn(p, tokens, targets, cfg, attn_impl=impl),
        has_aux=True,
    )(params)
    _families.assert_matches_reference(loss, grads, *reference(tokens.shape))
    if seq > sizes["sparse_dense_len"]:
        assert 6 <= float(aux["sparse"]["kv_blocks"]) <= 16
    else:
        assert aux == {}


def test_the_programs_choice_is_the_references(tiny):
    """``sparse_choice`` (the step's own code up to layer 0's choice) gives
    in float32 exactly the reference's choice; a choice handed to the
    reference is the one it attends."""
    sizes, cfg, params = tiny
    tokens, _ = _families.tokens(sizes, SHAPE)
    words = minicpm_sala.sparse_choice(params, tokens, cfg)
    program = np.asarray(sa.unpack_blocks(words, 16))
    with common.precision(jnp.float32):
        q, k = ref.first_sparse_qk(params, tokens, sizes, jnp.float32)
        chosen, ranked = ref.choice_scores(q, k, sizes, jnp.arange(128))
    np.testing.assert_array_equal(program, np.asarray(chosen))
    assert np.isinf(np.asarray(ranked)[..., 0]).all()  # block 0 forced
    # handing the reference its own choice changes nothing; another does
    with common.precision(jnp.float32):
        def given(choice):
            return np.asarray(ref.hidden(
                params, tokens, sizes, jnp.float32,
                choices=None if choice is None else [choice],
            ))

        own = given(None)
        np.testing.assert_allclose(given(jnp.asarray(program)), own, atol=1e-6)
        other = jnp.asarray(program).at[..., 64:, 1:10].set(True)
        assert np.abs(given(other) - own).max() > 1e-3


def test_the_paper_path_gives_the_native_tree():
    """``deferred_init`` of the torch module -> ``materialize_module_jax``
    -> ``convert``: every leaf filled, the native tree's shapes, norms of
    ones, the embedding's rows N(0, 1), the matrices N(0, 0.02)."""
    import torch

    import torchdistx_tpu.deferred_init as di
    import torchdistx_tpu.materialize as M

    sizes = _families.sizes(CONFIG)
    build, torch_config = family.hf(sizes)
    module = di.deferred_init(build, torch_config)
    _, cfg = family.native(sizes, jnp.float32)
    assert sum(p.numel() for p in module.parameters()) == minicpm_sala.num_params(cfg)
    arrays = M.materialize_module_jax(module, seed=3, dtype=torch.float32)
    params = family.to_params(arrays, cfg)
    assert jax.tree.map(jnp.shape, params) == jax.tree.map(
        lambda a: a.shape, minicpm_sala.abstract_params(cfg)
    )
    assert (np.asarray(params["lightning_layers"]["o_norm"]) == 1).all()
    assert 0.8 < float(np.asarray(params["embed"]["weight"]).std()) < 1.2
    assert 0.015 < float(np.asarray(params["sparse_layers"]["wq"]).std()) < 0.025
    tokens, _ = _families.tokens(sizes, SHAPE)
    logits = minicpm_sala.forward(params, tokens, cfg, attn_impl="jnp")
    assert np.isfinite(np.asarray(logits)).all()


def test_published_sizes():
    """The published layout and the cell's share: parameter counts as the
    issue's table has them."""
    full = minicpm_sala.MiniCPMSALAConfig()
    assert full.mixer_types.count(SPARSE) == 8 and full.n_layers == 32
    assert full.depth_scale == 1.4 / 32 ** 0.5
    cut = minicpm_sala.MiniCPMSALAConfig(
        n_layers=4, mixer_types=(SPARSE,) + (LIGHTNING,) * 3, vocab_size=18362
    )
    assert minicpm_sala.num_params(cut) == 1_259_865_088
    with pytest.raises(ValueError, match="mixer_types"):
        minicpm_sala.MiniCPMSALAConfig(n_layers=4)
    with pytest.raises(ValueError, match="disagree"):
        family.native(
            dict(_families.sizes(CONFIG), first_sparse_layer=1), jnp.float32
        )


def test_scopes_and_counters(tiny):
    """The names a trace is read by: ``attn/select``, ``attn/sparse`` with
    the three sparse kernels under the sparse layer, ``lightning/chunks``
    with the two lightning kernels, ``mlp``, ``stack``, ``embed``,
    ``head``; the ops' counters."""
    sizes, cfg, _ = tiny
    params = jax.eval_shape(lambda: minicpm_sala.init_params(jax.random.PRNGKey(0), cfg))
    tok = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    text = jax.jit(jax.grad(
        lambda p, t: minicpm_sala.loss_fn(p, t, t, cfg, attn_impl="pallas")[0]
    )).lower(params, tok).as_text(debug_info=True)
    c1, h1 = telemetry.counters(), telemetry.histograms()
    for scope in ("attn/select", "attn/sparse", "attn/qk_norm", "attn/gate",
                  "lightning/chunks", "lightning/rope", "lightning/o_norm",
                  "lightning/proj_in"):
        assert f"{scope}/" in text, scope
    assert "(stack)/" in text and "(embed)/" in text and "(head)/" in text
    for kernel in ("sparse_flash_fwd", "sparse_flash_bwd_dq", "sparse_flash_bwd_dkv",
                   "lightning_fwd", "lightning_bwd"):
        assert kernel in text, kernel

    def rose(name):
        return c1.get(name, 0) - c0.get(name, 0)

    # one scanned body: one sparse layer, three lightning layers
    assert rose("sparse_attn.mode{mode=sparse}") == 1
    assert rose("sparse_attn{impl=pallas}") == 1 and rose("sparse_attn{interpret=true}") == 1
    assert rose("lightning{impl=pallas}") == 3 and rose("lightning{interpret=true}") == 3
    assert h1["lightning.chunks"]["count"] - h0.get("lightning.chunks", {}).get("count", 0) == 3
    # dense below dense_len, through the flash kernels
    jax.jit(
        lambda p, t: minicpm_sala.loss_fn(p, t, t, cfg, attn_impl="pallas")[0]
    ).lower(params, jax.ShapeDtypeStruct((1, 32), jnp.int32))
    c2 = telemetry.counters()
    assert c2["sparse_attn.mode{mode=dense}"] - c1.get("sparse_attn.mode{mode=dense}", 0) == 1


def test_train_step_takes_the_family():
    """``make_train_step`` takes ``models.minicpm_sala`` as it takes the
    other families (``LOSS_HAS_AUX``): the loss falls, the sparse count
    comes out, on a mesh of eight devices through the jnp paths."""
    import optax

    from torchdistx_tpu.parallel import train_step as ts
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    cfg = minicpm_sala.minicpm_sala_test()
    mesh = make_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    init_fn, step_fn = ts.make_train_step(
        cfg, mesh, optax.adamw(1e-2), model=minicpm_sala, attn_impl="jnp"
    )
    tokens = jax.device_put(
        jax.random.randint(jax.random.PRNGKey(1), (4, 64), 0, cfg.vocab_size),
        ts.batch_sharding(mesh),
    )
    batch = {"tokens": tokens, "targets": tokens}
    state = init_fn(jax.random.PRNGKey(0))
    losses = []
    for _ in range(5):
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] and np.isfinite(losses).all()
    assert float(metrics["sparse"]["kv_blocks"]) > 0
    with pytest.raises(NotImplementedError, match="one device"):
        minicpm_sala.loss_fn(
            state.params, tokens, tokens, cfg, mesh=mesh, attn_impl="pallas"
        )
