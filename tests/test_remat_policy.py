"""What the scanned block's remat keeps: the block's input plus the flash
forward kernel's output and log-sum-exp (``flash_attention.REMAT_POLICY``),
so the backward pass never runs ``flash_fwd`` again.

Counts and exact values only: the CPU says nothing of the chip's time.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.ad_checkpoint import print_saved_residuals

from torchdistx_tpu.models import gpt2, llama
from torchdistx_tpu.ops.pallas.flash_attention import (
    _FUSED_BWD_DQ_VMEM,
    _FUSED_BWD_MAX_KV,
    REMAT_POLICY,
)

FAMILIES = {
    "gpt2": (gpt2, gpt2.gpt2_test),
    "llama": (llama, llama.llama_test),
}
families = pytest.mark.parametrize("family", sorted(FAMILIES))


def _cfg(family, **over):
    mod, make = FAMILIES[family]
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
    """The lowered gradient of a two-layer remat'ed model holds each flash
    kernel once: Mosaic's Python-side lowering needs no chip."""
    # The model resolves ``interpret`` from the default backend.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mod, cfg = _cfg(
        family, remat=True, max_seq_len=seq, dtype=jnp.bfloat16
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
    assert lowered.as_text().count("tpu_custom_call") == n_calls


@families
def test_remat_changes_no_bit(family):
    """Saved, not recomputed: the same values, so loss and every gradient
    leaf equal those of the model without remat exactly."""
    seq = 32
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, seq + 1), 0, 256)
    results = []
    for remat in (True, False):
        mod, cfg = _cfg(family, remat=remat)
        params = mod.init_params(jax.random.PRNGKey(0), cfg)
        results.append(
            jax.jit(_grad_fn(mod, cfg, "pallas"))(
                params, tokens[:, :-1], tokens[:, 1:]
            )
        )
    (loss_a, grads_a), (loss_b, grads_b) = results
    assert np.array_equal(loss_a, loss_b)
    leaves_a, leaves_b = jax.tree.leaves(grads_a), jax.tree.leaves(grads_b)
    assert len(leaves_a) == len(leaves_b) > 0
    for a, b in zip(leaves_a, leaves_b):
        assert np.array_equal(a, b)


def _block_residuals(family, impl, policy, capsys):
    """``(cfg, x, [(array type, where from), ...])``: what a checkpointed
    block saves, as ``print_saved_residuals`` lists it — one line each,
    ``f32[2,4,32] named 'flash_lse' from <source line>``."""
    mod, cfg = _cfg(family)
    block = mod._build_block(cfg, attn_impl=impl)
    layer = jax.tree.map(
        lambda leaf: leaf[0],
        mod.init_params(jax.random.PRNGKey(0), cfg)["layers"],
    )
    x = jnp.ones((2, 32, cfg.dim), cfg.dtype)
    capsys.readouterr()
    print_saved_residuals(jax.checkpoint(block, policy=policy), x, layer)
    lines = capsys.readouterr().out.splitlines()
    return cfg, x, [tuple(line.split(" ", 1)) for line in lines]


@families
def test_block_saves_its_input_and_the_two_named_arrays(family, capsys):
    cfg, x, saved = _block_residuals(family, "pallas", REMAT_POLICY, capsys)
    plain = _block_residuals(family, "pallas", None, capsys)[2]
    assert saved[: len(plain)] == plain
    batch, seq, _ = x.shape
    # The attention output in the model's layout (lane-dense when stacked
    # over layers) and one float32 log-sum-exp per head and row.  JAX
    # lists ``flash_out`` by the ``reduce_precision`` it wraps a saved
    # value in that is also the block's forward result.
    kept = dict(saved[len(plain):])
    lse = f"f32[{batch},{cfg.n_heads},{seq}]"
    out = f"f32[{batch},{seq},{cfg.n_heads * cfg.head_dim}]"
    assert sorted(kept) == sorted([lse, out]) and len(saved) == len(plain) + 2
    assert kept[lse].startswith("named 'flash_lse'")
    assert all("flash_attention.py" in why for why in kept.values())


@families
def test_policy_is_inert_without_the_kernel(family, capsys):
    """``attn_impl="jnp"`` names nothing: the block saves what a plain
    ``jax.checkpoint`` saves, its arguments."""
    saved = _block_residuals(family, "jnp", REMAT_POLICY, capsys)[2]
    assert saved == _block_residuals(family, "jnp", None, capsys)[2]
    assert saved and all(why.startswith("from the argument") for _, why in saved)
