"""Lightning attention (decayed linear attention): the chunked ``jnp``
path and the interpreted kernels against the quadratic form
``o_t = sum_{s <= t} lambda^(t-s) (q_t . k_s / sqrt(D)) v_s`` across chunk
boundaries, a head whose decay powers underflow, the backward against
``jax.grad`` of the quadratic form, and the counters.

CPU, float32, seeded: values and counts only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.ops import lightning_attention as la


def quadratic(q, k, v, log_decay):
    """The plain form: the whole ``(T, T)`` decay mask, no state."""
    t, d = q.shape[1], q.shape[-1]
    a = jnp.asarray(log_decay, jnp.float32)
    i = jnp.arange(t)
    diff = (i[:, None] - i[None, :]).astype(jnp.float32)
    mask = jnp.where(
        diff >= 0, jnp.exp(a[:, None, None] * jnp.maximum(diff, 0.0)), 0.0
    )
    s = jnp.einsum("bihd,bjhd->bhij", q, k) / jnp.sqrt(jnp.float32(d))
    return jnp.einsum("bhij,bjhd->bihd", s * mask, v)


def _qkv(t=80, h=4, d=16, b=2, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, t, h, d), jnp.float32) for k in keys)


# Two slow heads, one ordinary, one whose powers past a few positions are 0
# in float32 (exp(-40 n)).
DECAYS = (-0.004, -0.05, -0.7, -40.0)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("t,chunk", [(80, 16), (80, 32), (64, 64), (50, 16)])
def test_forward_matches_the_quadratic_form(impl, t, chunk):
    """Across chunk boundaries, a last chunk padded, one chunk alone."""
    q, k, v = _qkv(t=t)
    want = quadratic(q, k, v, DECAYS)
    got = la.lightning_attention(q, k, v, DECAYS, impl=impl, chunk=chunk)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_backward_matches_grad_of_the_quadratic_form(impl):
    q, k, v = _qkv(t=96)
    w = jax.random.normal(jax.random.PRNGKey(7), q.shape)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * w).sum()

    want = jax.grad(loss(lambda *x: quadratic(*x, DECAYS)), argnums=(0, 1, 2))(q, k, v)
    got = jax.grad(
        loss(lambda *x: la.lightning_attention(*x, DECAYS, impl=impl, chunk=32)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=1e-5)


def test_the_fast_head_underflows_and_nothing_overflows():
    """A decay of exp(-40) a position: its powers reach 0 in float32 within
    three positions, no power is ever formed with a negative exponent, so
    nothing is infinite and that head reads its own position's product
    alone."""
    q, k, v = _qkv(t=64, h=1)
    got = la.lightning_attention(q, k, v, (-40.0,), impl="pallas", chunk=32)
    g = jax.grad(
        lambda q: la.lightning_attention(q, k, v, (-40.0,), impl="pallas", chunk=32).sum()
    )(q)
    assert np.isfinite(np.asarray(got)).all() and np.isfinite(np.asarray(g)).all()
    own = (q * k).sum(-1, keepdims=True) / 4.0 * v
    np.testing.assert_allclose(np.asarray(got), np.asarray(own), atol=1e-5)


def test_kernels_agree_with_the_jnp_path_in_bfloat16():
    q, k, v = (x.astype(jnp.bfloat16) for x in _qkv(t=128, h=2, d=32))
    decays = la.lightning_decays(2)
    a = la.lightning_attention(q, k, v, decays, impl="jnp", chunk=64)
    b = la.lightning_attention(q, k, v, decays, impl="pallas", chunk=64)
    assert a.dtype == b.dtype == jnp.bfloat16
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    # bfloat16 rounding of the quadratic form's operands, nothing more
    assert np.linalg.norm(a - b) / np.linalg.norm(a) < 0.01


def test_decays_are_the_alibi_slopes():
    d = la.lightning_decays(32)
    assert len(d) == 32 and d[0] == -(2.0 ** -0.25) and d[-1] == -(2.0 ** -8)
    assert all(x < y < 0 for x, y in zip(d, d[1:]))
    with pytest.raises(ValueError):
        la.lightning_attention(*_qkv(h=2), (-0.1, 0.2), impl="jnp")


def test_counters_and_the_kept_names():
    q, k, v = _qkv(t=100)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    jaxpr = jax.make_jaxpr(jax.vjp, static_argnums=0)(
        lambda q: la.lightning_attention(q, k, v, DECAYS, impl="pallas", chunk=32), q
    )
    c1, h1 = telemetry.counters(), telemetry.histograms()
    assert c1.get("lightning{impl=pallas}", 0) - c0.get("lightning{impl=pallas}", 0) == 1
    assert c1.get("lightning{interpret=true}", 0) > c0.get("lightning{interpret=true}", 0)
    assert h1["lightning.chunks"]["max"] >= 4
    text = str(jaxpr)
    assert "lightning_out" in text and "lightning_starts" in text
