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
