"""What the scanned block's remat keeps (``ops.remat.REMAT_POLICY``): the
block's input plus what the kernels it ran name — the flash forward
kernel's output and log-sum-exp, the selective scan's output and
chunk-start states, the routed-expert layer's first gate and up products
and its result — so the backward pass never runs ``flash_fwd``,
``ssm_scan_fwd`` or a forward grouped product again.

Counts and exact values only: the CPU says nothing of the chip's time.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from torchdistx_tpu.models import (
    afmoe, deepseek_v3, gpt2, jamba, llama, smallthinker,
)
from torchdistx_tpu.ops import routed_experts as routed_mod
from torchdistx_tpu.ops.pallas.flash_attention import (
    _FUSED_BWD_DQ_VMEM,
    _FUSED_BWD_MAX_KV,
)
from torchdistx_tpu.ops.remat import REMAT_POLICY

# The families with a routed-expert layer, and the expert layers one copy
# of their scanned body holds: ``deepseek_v3_test`` scans two like layers,
# ``afmoe_test`` runs its one period (window, window, full) unrolled.
ROUTED = {
    "deepseek_v3": (deepseek_v3, deepseek_v3.deepseek_v3_test, 1),
    "afmoe": (afmoe, afmoe.afmoe_test, 3),
}
# ``jamba_test``: two periods under one scan, in each a Mamba stack of one
# layer, the attention layer, a Mamba stack of two.
FAMILIES = {
    "gpt2": (gpt2, gpt2.gpt2_test),
    "llama": (llama, llama.llama_test),
    "jamba": (jamba, jamba.jamba_test),
    **{name: entry[:2] for name, entry in ROUTED.items()},
    # Every layer an expert layer whose router reads the layer's input:
    # two periods of four layers under one scan.
    "smallthinker": (smallthinker, smallthinker.smallthinker_test),
}
families = pytest.mark.parametrize("family", ["gpt2", "jamba", "llama"])
routed = pytest.mark.parametrize("family", sorted(ROUTED))
every_family = pytest.mark.parametrize("family", sorted(FAMILIES))
impls = pytest.mark.parametrize("impl", ["jnp", "pallas"])


def _cfg(family, impl, *, seq=None, **over):
    """The family's test configuration; ``impl`` is the attention's and,
    in a state-space family, the scan's too."""
    mod, make = FAMILIES[family]
    if family == "jamba":
        over["scan_impl"] = impl
    elif seq is not None:
        over["max_seq_len"] = seq
    return mod, dataclasses.replace(make(), **over)


def _expert_block(family, cfg, impl):
    """An expert layer of a routed family as ``x, lp -> (x, stats)``, not
    rematerialised (afmoe builds a block a kind: the full layer's)."""
    moe = FAMILIES[family][0]._build_blocks(cfg, attn_impl=impl)[1]
    return moe(afmoe.FULL) if family == "afmoe" else moe


def _grad_fn(mod, cfg, impl):
    """``(loss, grads)``; a family whose loss carries counts out
    (``LOSS_HAS_AUX``) gives them beside the loss and they are dropped."""
    has_aux = getattr(mod, "LOSS_HAS_AUX", False)
    fn = jax.value_and_grad(
        lambda p, t, y: mod.loss_fn(p, t, y, cfg, attn_impl=impl),
        has_aux=has_aux,
    )
    if not has_aux:
        return fn

    def loss_and_grads(*args):
        (loss, _), grads = fn(*args)
        return loss, grads

    return loss_and_grads


@families
@pytest.mark.parametrize(
    "seq,n_calls",
    [
        (1024, 2),  # flash_fwd + flash_bwd_fused (3 with the forward rerun)
        # Several kv blocks: still one backward kernel (the pair until PR 28)
        (2 * _FUSED_BWD_MAX_KV, 2),
        # dq (f32, 128 lanes a head) at twice its VMEM budget: flash_fwd +
        # the streamed pair (4 with the forward rerun)
        (2 * _FUSED_BWD_DQ_VMEM // (128 * 4), 3),
    ],
)
def test_backward_holds_no_second_flash_fwd(family, seq, n_calls, monkeypatch):
    """The lowered gradient of a remat'ed model holds each kernel once a
    scanned stack (the flash kernels once, and in the state-space family
    ``ssm_scan_fwd`` and ``ssm_scan_bwd`` once for each of the period's two
    Mamba stacks; six scan calls with the forward rerun): Mosaic's
    Python-side lowering needs no chip."""
    # The model resolves ``interpret`` from the default backend.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod, cfg = _cfg(
        family, "pallas", remat=True, seq=seq, dtype=jnp.bfloat16
    )
    params = jax.eval_shape(
        lambda: mod.init_params(jax.random.PRNGKey(0), cfg)
    )
    tok = jax.ShapeDtypeStruct((1, seq), jnp.int32)
    lowered = (
        jax.jit(_grad_fn(mod, cfg, "pallas"))
        .trace(params, tok, tok)
        .lower(lowering_platforms=("tpu",))
    )
    text = lowered.as_text()
    scans = {
        name: text.count(f'kernel_name = "{name}"')
        for name in ("ssm_scan_fwd", "ssm_scan_bwd")
    }
    stacks = 2 if family == "jamba" else 0
    assert scans == {"ssm_scan_fwd": stacks, "ssm_scan_bwd": stacks}
    assert text.count("tpu_custom_call") == n_calls + 2 * stacks


# Where remat off and on may differ in a sum's last bits: XLA:CPU adds up
# the Mamba layers' norm-weight and convolution gradients over B x T in
# another order in the two programs (1.4e-9 of 3.9e-3, with or without the
# kept arrays: a plain ``jax.checkpoint`` reads the same).  An expert
# family's three programs are fused apart the same way (up to 9.2e-7 of a
# leaf's largest entry), the one that keeps the products from the one that
# recomputes them too: op by op they agree bit for bit (the test below).
FUSED_APART = dict.fromkeys([*ROUTED, "smallthinker"], 2e-6)
SUM_ORDER = {"jamba": 1e-6, **FUSED_APART}


def _gap(a, b):
    return np.abs(a - b).max() / max(np.abs(b).max(), 1e-30)


@every_family
@impls
def test_remat_changes_no_bit(family, impl, monkeypatch):
    """Saved, not recomputed: the same values (``pallas``: through the
    interpreter).  Loss and every gradient leaf equal, bit for bit, those
    of a block that keeps nothing and recomputes the kernels (an expert
    family's up to ``FUSED_APART``), and those of the model without remat,
    the latter up to ``SUM_ORDER``."""
    seq = 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq + 1), 0, 256)

    def run(remat):
        mod, cfg = _cfg(family, impl, remat=remat)
        params = mod.init_params(jax.random.PRNGKey(0), cfg)
        loss, grads = jax.jit(_grad_fn(mod, cfg, impl))(
            params, tokens[:, :-1], tokens[:, 1:]
        )
        return [loss] + jax.tree.leaves(grads)

    kept, without = run(True), run(False)
    monkeypatch.setattr(FAMILIES[family][0], "REMAT_POLICY", None)
    recomputed = run(True)
    assert len(kept) == len(recomputed) == len(without) > 1
    assert np.array_equal(kept[0], recomputed[0])
    assert np.array_equal(kept[0], without[0])
    for a, b, c in zip(kept, recomputed, without):
        assert _gap(a, b) <= FUSED_APART.get(family, 0.0)
        assert _gap(a, c) <= SUM_ORDER.get(family, 0.0)


@routed
def test_kept_products_are_the_recomputed_ones_bit_for_bit(family):
    """An expert block's output and gradients (its input's, every
    parameter's) with the named arrays kept, with everything recomputed
    and without remat, each primitive run on its own so that no compiler
    fuses the three programs apart: equal, bit for bit."""
    mod, cfg = _cfg(family, "jnp")
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    block = _expert_block(family, cfg, "jnp")
    layer = jax.tree.map(lambda leaf: leaf[0], params["moe_layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.dim), cfg.dtype)
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape, cfg.dtype)

    def run(fn):
        with jax.disable_jit():
            out, grads = jax.value_and_grad(
                lambda x, lp: (fn(x, lp)[0] * cot).sum(), argnums=(0, 1)
            )(x, layer)
        return [out] + jax.tree.leaves(grads)

    kept = run(jax.checkpoint(block, policy=REMAT_POLICY))
    assert len(kept) > 2
    for other in (run(jax.checkpoint(block)), run(block)):
        for a, b in zip(kept, other, strict=True):
            assert np.array_equal(a, b)


def _block_residuals(family, impl, policy, capsys, block="kernel", mesh=None):
    """``(cfg, x, [(array type, where from), ...])``: what a checkpointed
    block saves, as ``print_saved_residuals`` lists it — one line each,
    ``f32[2,4,32] named 'flash_lse' from <source line>``.  In the
    state-space family ``block="kernel"`` is the Mamba block and
    ``block="attn"`` the attention block, which holds no scan."""
    mod, cfg = _cfg(family, impl)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    if family in ROUTED:
        fn = _expert_block(family, cfg, impl)
        layers, lead = params["moe_layers"], (0,)
    elif family == "jamba":
        mamba, attn = mod._build_blocks(cfg, mesh=mesh, attn_impl=impl)
        fn, stack, lead = (
            (attn, "attn", (0,)) if block == "attn" else (mamba, "mamba_b", (0, 0))
        )
        layers = params["periods"][stack]
    else:
        fn, layers, lead = mod._build_block(cfg, attn_impl=impl), params["layers"], (0,)
    layer = jax.tree.map(lambda leaf: leaf[lead], layers)
    x = jnp.ones((2, 32, cfg.dim), cfg.dtype)
    capsys.readouterr()
    print_saved_residuals(jax.checkpoint(fn, policy=policy), x, layer)
    lines = capsys.readouterr().out.splitlines()
    return cfg, x, [tuple(line.split(" ", 1)) for line in lines]


def _named(family, cfg, x):
    """``{array type: (name, source file)}``: the two arrays a block's
    kernel names, in the shapes a scan over the layers stacks."""
    batch, seq, _ = x.shape
    if family == "jamba":
        # The scan's output in the model's dtype and one float32 state of
        # ``d_state`` per channel and time chunk, the kernels' layout.
        chunks = seq // cfg.scan_chunk
        return {
            f"f32[{batch},{seq},{cfg.d_inner}]": ("ssm_out", "selective_scan.py"),
            f"f32[{batch},{chunks},{cfg.d_state},{cfg.d_inner}]": (
                "ssm_starts", "selective_scan.py"
            ),
        }
    # The attention output in the model's layout (lane-dense when stacked
    # over layers) and one float32 log-sum-exp per head and row.
    return {
        f"f32[{batch},{seq},{cfg.n_heads * cfg.head_dim}]": (
            "flash_out", "flash_attention.py"
        ),
        f"f32[{batch},{cfg.n_heads},{seq}]": ("flash_lse", "flash_attention.py"),
    }


@pytest.mark.parametrize(
    "family,impl",
    [("gpt2", "pallas"), ("llama", "pallas"), ("jamba", "jnp"), ("jamba", "pallas")],
)
def test_block_saves_its_input_and_the_two_named_arrays(family, impl, capsys):
    """The block's arguments plus exactly the two arrays its kernel names:
    an attention block saves no scan's, a Mamba block no attention's, and
    the scan names its own through either ``impl`` (one ``custom_vjp``)."""
    cfg, x, saved = _block_residuals(family, impl, REMAT_POLICY, capsys)
    plain = _block_residuals(family, impl, None, capsys)[2]
    assert saved[: len(plain)] == plain
    kept, want = dict(saved[len(plain):]), _named(family, cfg, x)
    assert sorted(kept) == sorted(want) and len(saved) == len(plain) + 2
    for array, (name, source) in want.items():
        # JAX lists a saved value that is also the VJP's result by the
        # ``reduce_precision`` it wraps it in, the other by its name.
        assert source in kept[array]
        if name in ("flash_lse", "ssm_starts"):
            assert kept[array].startswith(f"named '{name}'")


def test_a_mamba_block_on_a_mesh_keeps_each_shards_scan(capsys):
    """On a mesh the kernels run a scan a shard (``shard_map``: rows over
    dp, channels over tp) and the names sit inside its body: the block
    still saves its arguments and the two arrays, as ``shard_map`` hands
    them out (the chunk-start states stacked over the four shards)."""
    from torchdistx_tpu.parallel.mesh import MeshSpec, make_mesh

    mesh = make_mesh(MeshSpec(dp=2, tp=2), devices=jax.devices()[:4])
    cfg, x, saved = _block_residuals(
        "jamba", "pallas", REMAT_POLICY, capsys, mesh=mesh
    )
    plain = _block_residuals("jamba", "pallas", None, capsys, mesh=mesh)[2]
    assert saved[: len(plain)] == plain and len(saved) == len(plain) + 2
    batch, seq, _ = x.shape
    chunks, shard = seq // cfg.scan_chunk, cfg.d_inner // 2
    assert sorted(array for array, _ in saved[len(plain):]) == sorted([
        f"f32[{batch},{seq},{cfg.d_inner}]",
        f"f32[{4 * batch // 2},{chunks},{cfg.d_state},{shard}]",
    ])


@families
def test_policy_is_inert_without_the_kernel(family, capsys):
    """``attn_impl="jnp"`` names nothing, and a block without a scan saves
    nothing for one: it saves what a plain ``jax.checkpoint`` saves, its
    arguments."""
    saved = _block_residuals(family, "jnp", REMAT_POLICY, capsys, "attn")[2]
    assert saved == _block_residuals(family, "jnp", None, capsys, "attn")[2]
    assert saved and all(why.startswith("from the argument") for _, why in saved)


def _count(jaxpr, primitive, *, in_loop=False, found=None):
    """``{"once": n, "loop": n}``: the equations of ``primitive`` a jaxpr
    holds outside every ``while`` and inside one, the bodies of its scans,
    calls, remats and custom rules included."""
    found = {"once": 0, "loop": 0} if found is None else found
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == primitive:
            found["loop" if in_loop else "once"] += 1
        inside = in_loop or eqn.primitive.name == "while"
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _count(sub, primitive, in_loop=inside, found=found)
    return found


@routed
@pytest.mark.parametrize("remat", [True, False])
def test_an_expert_layer_runs_no_grouped_product_twice(family, remat):
    """The gradient holds, an expert layer, the first chunk's 3 forward
    and 6 backward grouped products and no replay of the sorted side (12
    before the forward was kept, 15 where a norm follows the layer and the
    remat replayed it whole); the loops over the chunks of the overflow
    hold 3 forward and 2 recomputed + 6 backward, and one more each way
    that adds a chunk's rows to their tokens (``_add_rows``).  Without
    remat the same rule holds the two products as ordinary residuals."""
    mod, make, layers = ROUTED[family]
    cfg = dataclasses.replace(make(), remat=remat)
    params = jax.eval_shape(lambda: mod.init_params(jax.random.PRNGKey(0), cfg))
    tok = jnp.zeros((2, 32), jnp.int32)
    jaxpr = jax.make_jaxpr(
        jax.grad(lambda p: mod.loss_fn(p, tok, tok, cfg, attn_impl="jnp")[0])
    )(params)
    assert _count(jaxpr.jaxpr, "ragged_dot_general") == {
        "once": (3 + 6) * layers, "loop": (3 + 1 + 2 + 6 + 1) * layers,
    }


@routed
def test_an_expert_block_saves_its_products_and_its_result_where_needed(
    family, capsys
):
    """Of its arguments an expert block keeps what a plain
    ``jax.checkpoint`` keeps but the selection bias (the choice it biased
    is kept, so no replay selects again), and beside them the forward's
    choice ``(T, k)``, the first chunk's gate and up products ``(R, F)``
    and, ONLY where its backward reads the layer's result (afmoe: a norm
    follows it), the result ``(T, D)``; where the block ends ``x + out``
    (deepseek_v3) nothing asks for it and nothing is stored."""
    cfg, x, saved = _block_residuals(family, "jnp", REMAT_POLICY, capsys)
    plain = _block_residuals(family, "jnp", None, capsys)[2]
    assert all(why.startswith("from the argument") for _, why in plain)
    arguments = [entry for entry in saved if entry in plain]
    assert [entry for entry in plain if entry not in arguments] == [
        (f"f32[{cfg.n_experts}]", "from the argument lp['router_bias']")
    ]
    tokens = x.shape[0] * x.shape[1]
    rows = routed_mod._row_bound(
        tokens * cfg.experts_per_token, cfg.held, cfg.n_experts
    )
    products = f"f32[{rows},{cfg.expert_dim}]"  # moe_gate, moe_up
    result = [f"f32[{tokens},{cfg.dim}]"] if family == "afmoe" else []  # moe_out
    selected = f"i32[{tokens},{cfg.experts_per_token}]"
    # JAX lists a saved value that leaves a VJP's forward rule by the
    # ``reduce_precision`` it wraps it in, not by its name.
    kept = [entry for entry in saved if entry not in plain]
    choice = [why for array, why in kept if array == selected]
    # the choice, and the same indices as ``take_along_axis`` wraps them
    assert len(choice) == 2 and choice[0].startswith("named 'moe_selected'")
    arrays = sorted(array for array, _ in kept if array != selected)
    assert arrays == sorted([products] * 2 + result)
    assert all("routed_experts.py" in why for _, why in kept)


@pytest.mark.parametrize("family", sorted([*ROUTED, "smallthinker"]))
def test_kept_products_are_rows_of_the_order_the_forward_sorted(family):
    """bfloat16, where a replay's scores round otherwise than the
    forward's and a near-tied choice flips: the kept products are rows of
    the forward's sorted order, so the block keeps the forward's choice
    (``moe_selected``) with them and the replay sorts by it.  Every
    gradient leaf with remat within 0.06 of the one without (read: 0.024
    at worst; 0.71 with the products kept and the choice recomputed, every
    row after the first flipped choice belonging to another token; 0.13
    before anything was kept, the replay consistent with itself alone)."""
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 65), 0, 256)

    def run(remat):
        mod, cfg = _cfg(family, "jnp", remat=remat, dtype=jnp.bfloat16)
        params = mod.init_params(jax.random.PRNGKey(0), cfg)
        return jax.tree.leaves(jax.jit(_grad_fn(mod, cfg, "jnp"))(
            params, tokens[:, :-1], tokens[:, 1:]
        ))

    for a, b in zip(run(True), run(False), strict=True):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.linalg.norm(a - b) <= 0.06 * np.linalg.norm(b)
