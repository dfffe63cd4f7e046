"""The families the benchmark already runs lower as the parent commit
(33cfd3f, PR 38) lowers them, now that the remat policy keeps five more
names (``sparse_choice``, ``sparse_out``, ``sparse_lse``,
``lightning_out``, ``lightning_starts``) and two kernel files are new: the
gradient of each family's test model, remat on, bfloat16, its kernels, its
StableHLO lowered for the TPU with each Mosaic body printed without its
source locations (``test_attention._without_locations``), hashes to what
the parent gives.  A PR that changes one of these programs on purpose
re-pins its hash."""

import dataclasses
import hashlib
import importlib

import jax
import jax.numpy as jnp
import pytest

from test_attention import _without_locations

PINNED = {
    "gpt2": "1015e9db8093d0784563a030758a7b53308fd4694cbb6b2256c32d6ef111fb17",
    "llama": "e0513dc801317b3e7abc8e1b14cdde0ecf145f95a6b4f9aa0de168f0a41535de",
    "deepseek_v3": "54761d3331ab71cd86fbdd777cc854a5856c9375924e2daac47e7cc448cada9b",
    "afmoe": "c4a25f815b80c5d3125d2633ea67bce511953fca876894e7a46b4fcbfc33311d",
    "jamba": "9178c251258d5aa11b0f579346cb6a71c231fa76d6e080dd251bdf6917dfdd06",
    "smallthinker": "915a920ef8d9cf2c5ef76e86c14ea7a0c6232acae71fe0d30b984f65ddf0de57",
}


def lowered(name: str) -> str:
    """The TPU StableHLO of ``jax.grad`` of family ``name``'s loss at its
    test size, remat on, bfloat16, Pallas kernels."""
    model = importlib.import_module(f"torchdistx_tpu.models.{name}")
    cfg = getattr(model, f"{name}_test")()
    over = {"dtype": jnp.bfloat16}
    if "remat" in {f.name for f in dataclasses.fields(cfg)}:
        over["remat"] = True
    cfg = dataclasses.replace(cfg, **over)
    params = model.abstract_params(cfg)
    tok = jax.ShapeDtypeStruct((2, 128), jnp.int32)

    def loss(p, t):
        out = model.loss_fn(p, t, t, cfg, attn_impl="pallas")
        return out[0] if getattr(model, "LOSS_HAS_AUX", False) else out

    return jax.jit(jax.grad(loss)).trace(params, tok).lower(
        lowering_platforms=("tpu",)
    ).as_text()


def digest(name: str) -> str:
    return hashlib.sha256(_without_locations(lowered(name)).encode()).hexdigest()


@pytest.mark.parametrize("name", sorted(PINNED))
def test_family_lowers_as_the_parent(name, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert digest(name) == PINNED[name]
