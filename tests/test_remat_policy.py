"""What the scanned block's remat keeps (``ops.remat.REMAT_POLICY``): the
block's input plus what the kernels it ran name — the flash forward
kernel's output and log-sum-exp, the selective scan's output and
chunk-start states — so the backward pass never runs ``flash_fwd`` or
``ssm_scan_fwd`` again.

Counts and exact values only: the CPU says nothing of the chip's time.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from torchdistx_tpu.models import gpt2, jamba, llama
from torchdistx_tpu.ops.pallas.flash_attention import (
    _FUSED_BWD_DQ_VMEM,
    _FUSED_BWD_MAX_KV,
)
from torchdistx_tpu.ops.remat import REMAT_POLICY

# ``jamba_test``: two periods under one scan, in each a Mamba stack of one
# layer, the attention layer, a Mamba stack of two.
FAMILIES = {
    "gpt2": (gpt2, gpt2.gpt2_test),
    "llama": (llama, llama.llama_test),
    "jamba": (jamba, jamba.jamba_test),
}
families = pytest.mark.parametrize("family", sorted(FAMILIES))
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


def _grad_fn(mod, cfg, impl):
    return jax.value_and_grad(
        lambda p, t, y: mod.loss_fn(p, t, y, cfg, attn_impl=impl)
    )


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
# kept arrays: a plain ``jax.checkpoint`` reads the same).
SUM_ORDER = {"jamba": 1e-6}


@families
@impls
def test_remat_changes_no_bit(family, impl, monkeypatch):
    """Saved, not recomputed: the same values (``pallas``: through the
    interpreter).  Loss and every gradient leaf equal, bit for bit, those
    of a block that keeps nothing and recomputes the kernels, and those of
    the model without remat, the latter up to ``SUM_ORDER``."""
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
    for a, b in zip(kept, recomputed):
        assert np.array_equal(a, b)
    assert np.array_equal(kept[0], without[0])
    for a, b in zip(kept, without):
        gap = SUM_ORDER.get(family, 0.0) * np.abs(b).max()
        assert np.abs(a - b).max() <= gap


def _block_residuals(family, impl, policy, capsys, block="kernel", mesh=None):
    """``(cfg, x, [(array type, where from), ...])``: what a checkpointed
    block saves, as ``print_saved_residuals`` lists it — one line each,
    ``f32[2,4,32] named 'flash_lse' from <source line>``.  In the
    state-space family ``block="kernel"`` is the Mamba block and
    ``block="attn"`` the attention block, which holds no scan."""
    mod, cfg = _cfg(family, impl)
    params = mod.init_params(jax.random.PRNGKey(0), cfg)
    if family == "jamba":
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
