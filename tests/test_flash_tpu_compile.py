"""The flash kernels compiled for a described v5e, no chip attached: what
Mosaic and XLA:TPU refuse on the chip (a block that does not tile, more
VMEM than a kernel may take) they refuse here.  Nothing runs, so nothing
here is a time or a result.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU's library, and every xdist worker imports every
test file.  Keep such tests in this one file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from torchdistx_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "seq,hq,hkv,d_qk,d_v,backward",
    [
        # kanana-2-30b-a3b's latent attention at 8k: dq takes 8 MiB of VMEM
        (8192, 2, 2, 192, 128, ["flash_bwd_fused"]),
        # v padded to q's width: the streamed dk/dv kernel does not fit
        # Mosaic's default 16 MiB at these blocks, the one kernel states
        # its own limit
        (8192, 2, 2, 192, 192, ["flash_bwd_fused"]),
        # a GQA group of four shares one kv head's dq block (Mistral-7B, 4k)
        (4096, 8, 2, 128, 128, ["flash_bwd_fused"]),
        # the VMEM budget's edge, 16 MiB of dq, and twice that
        (32768, 1, 1, 128, 128, ["flash_bwd_fused"]),
        # Olmo-Hybrid-7B's full layer at 16k: 30 heads on 30, a group of
        # one, 8 MiB of dq
        (16384, 30, 30, 128, 128, ["flash_bwd_fused"]),
        (65536, 1, 1, 128, 128, ["flash_bwd_dkv", "flash_bwd_dq"]),
    ],
)
def test_backward_compiles_for_a_v5e(
    one_chip, seq, hq, hkv, d_qk, d_v, backward
):
    def spec(heads, width):
        return jax.ShapeDtypeStruct(
            (1, seq, heads, width), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(spec(hq, d_qk), spec(hkv, d_qk), spec(hkv, d_v))
        .compile()
    )
    text = compiled.as_text()
    kernels = sorted(
        name
        for name in ("flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv")
        if name in text
    )
    assert kernels == backward
    assert "flash_fwd" in text


def test_group_of_twenty_on_one_head_takes_the_streamed_pair(one_chip):
    """AI21-Jamba2-3B's attention layer at the cell's 4,096 positions: 20
    query heads on ONE key/value head of 128; the group's f32 dq is 40 MiB,
    past the one kernel's budget, so the backward is the streamed pair."""
    def spec(heads):
        return jax.ShapeDtypeStruct(
            (1, 4096, heads, 128), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        out = fa.flash_attention(q, k, v, causal=True, interpret=False)
        return out.astype(jnp.float32).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(spec(20), spec(1), spec(1)).compile().as_text()
    )
    assert "flash_fwd" in text and "flash_bwd_fused" not in text
    assert "flash_bwd_dq" in text and "flash_bwd_dkv" in text


@pytest.mark.parametrize(
    "window,hq,hkv,kernels",
    [
        # Trinity-Mini's window layers at the cell's shape: 32 query heads
        # on 4 key/value heads of 128, window 2,048 at 8,192 positions; the
        # group's f32 dq is 32 MiB, so the backward is the streamed pair
        (2048, 32, 4, ["flash_win_bwd_dkv", "flash_win_bwd_dq", "flash_win_fwd"]),
        # ... its full layer at the same shape: the plain kernels
        (None, 32, 4, ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]),
        # one head a group: the one-kernel backward through a band
        (2048, 4, 4, ["flash_win_bwd_fused", "flash_win_fwd"]),
        # a window off the blocks' grain
        (1500, 8, 4, ["flash_win_bwd_fused", "flash_win_fwd"]),
    ],
)
def test_window_kernels_compile_for_a_v5e(one_chip, window, hq, hkv, kernels):
    def spec(heads):
        return jax.ShapeDtypeStruct(
            (1, 8192, heads, 128), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=True, interpret=False, window=window
        )
        return out.astype(jnp.float32).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(spec(hq), spec(hkv), spec(hkv)).compile().as_text()
    )
    names = (
        "flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv",
        "flash_win_fwd", "flash_win_bwd_fused", "flash_win_bwd_dq",
        "flash_win_bwd_dkv",
    )
    # no plain name is a part of a window kernel's, nor the other way
    assert sorted(n for n in names if n in text) == kernels


@pytest.mark.parametrize(
    "window,kernels",
    [
        (4096, ["flash_win_bwd_dkv", "flash_win_bwd_dq", "flash_win_fwd"]),
        (None, ["flash_bwd_dkv", "flash_bwd_dq", "flash_fwd"]),
    ],
)
def test_a_group_of_seven_at_16k_compiles_for_a_v5e(one_chip, window, kernels):
    """SmallThinker-21BA3B's attention at its full 16,384 positions: 28
    query heads on 4 key/value heads of 128, a group of SEVEN, the window
    layers' band of 4,096 (5 kv blocks a q block) and the full layers'
    triangle; a group's f32 dq is 7 x 16,384 x 128 x 4 = 58.7 MB, so both
    backward passes are the streamed pair."""
    def spec(heads):
        return jax.ShapeDtypeStruct(
            (1, 16384, heads, 128), jnp.bfloat16, sharding=one_chip
        )

    def loss(q, k, v):
        out = fa.flash_attention(
            q, k, v, causal=True, interpret=False, window=window
        )
        return out.astype(jnp.float32).sum()

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(spec(28), spec(4), spec(4)).compile().as_text()
    )
    names = (
        "flash_fwd", "flash_bwd_fused", "flash_bwd_dq", "flash_bwd_dkv",
        "flash_win_fwd", "flash_win_bwd_fused", "flash_win_bwd_dq",
        "flash_win_bwd_dkv",
    )
    assert sorted(n for n in names if n in text) == kernels


@pytest.mark.parametrize("seq,chunk", [(4096, 128), (8192, 128), (4096, 256)])
def test_selective_scan_kernels_compile_for_a_v5e(one_chip, seq, chunk):
    """``ssm_scan_fwd`` and ``ssm_scan_bwd`` at AI21-Jamba2-3B's widths
    (5,120 channels, 16 states, bfloat16): the blocks tile, and the
    backward's recomputed states fit the VMEM the kernels ask for."""
    from torchdistx_tpu.ops.pallas import selective_scan as kernels

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    wide, narrow = spec((1, seq, 5120)), spec((1, seq, 16))
    a, d = spec((5120, 16), jnp.float32), spec((5120,))
    forward = jax.jit(
        lambda *x: kernels.forward(*x, chunk=chunk, interpret=False)
    ).lower(wide, wide, a, narrow, narrow, d).compile()
    assert "ssm_scan_fwd" in forward.as_text()
    starts = spec((1, seq // chunk, 16, 5120), jnp.float32)
    backward = jax.jit(
        lambda *x: kernels.backward(*x, chunk=chunk, interpret=False)
    ).lower(wide, wide, a, narrow, narrow, d, starts, wide).compile()
    assert "ssm_scan_bwd" in backward.as_text()


@pytest.mark.parametrize("chunk", [128])
def test_delta_rule_kernels_compile_for_a_v5e(one_chip, chunk):
    """``delta_rule_fwd`` and ``delta_rule_bwd`` at Olmo-Hybrid-7B's widths
    (30 heads, keys of 96 and values of 192, neither a multiple of the 128
    lanes; bfloat16) over 16,384 positions: the blocks tile, a step's row
    of the gates is read and written where it lies, and the chunk's tiles
    fit the VMEM the kernels ask for."""
    from torchdistx_tpu.ops.pallas import delta_rule as kernels

    def spec(shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    seq, heads = 16384, 30
    qk, v = spec((1, heads, seq, 96)), spec((1, heads, seq, 192))
    gate = spec((1, heads, seq), jnp.float32)
    forward = jax.jit(
        lambda *x: kernels.forward(*x, chunk=chunk, interpret=False)
    ).lower(qk, qk, v, gate, gate).compile()
    assert "delta_rule_fwd" in forward.as_text()
    starts = spec((1, heads, seq // chunk, 96, 192), jnp.float32)
    backward = jax.jit(
        lambda *x: kernels.backward(*x, chunk=chunk, interpret=False)
    ).lower(qk, qk, v, gate, gate, starts, v).compile()
    assert "delta_rule_bwd" in backward.as_text()
