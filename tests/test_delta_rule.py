"""The gated delta rule: the chunked ``jnp`` path and the interpreted kernels
against the per-token recurrence ``S_t = e^{g_t} (I - beta_t k_t k_t^T)
S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, forward and gradients in
q, k, v, g and beta: beta near 2, a gate near -20, a sequence that is not a
whole number of chunks, one chunk and several; the counters and the names
a remat policy keeps.

CPU, float32, seeded: values and counts only.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.ops import delta_rule as dr

# (T, chunk): several whole chunks, one chunk, a last chunk padded.  Every
# case runs three heads: ordinary gates, a gate of about -20 a position,
# and beta held near 2 with almost no decay.
CASES = {"several_chunks": (64, 16), "one_chunk": (24, 32), "padded": (50, 16)}
GATE_SCALE = (1.0, 20.0, 0.01)
BETA_NEAR_2 = (False, False, True)


def _inputs(t, b=1, dk=8, dv=16):
    """Unit keys, queries of length ``d_k^-1/2``, gates ``-scale *
    softplus(.)`` with head ``h``'s scale ``GATE_SCALE[h]``, beta in
    ``(0, 2)``, near 2 in the heads ``BETA_NEAR_2`` marks."""
    h = len(GATE_SCALE)
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    q = jax.random.normal(ks[0], (b, t, h, dk))
    k = jax.random.normal(ks[1], (b, t, h, dk))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / dk**0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, dv))
    g = -jnp.asarray(GATE_SCALE) * jax.nn.softplus(jax.random.normal(ks[3], (b, t, h)))
    shift = jnp.where(jnp.asarray(BETA_NEAR_2), 6.0, 0.0)
    beta = 2.0 * jax.nn.sigmoid(shift + jax.random.normal(ks[4], (b, t, h)))
    return q, k, v, g, beta


def _loss(fn):
    """A weighted sum of the output, so that every position's gradient
    differs."""
    def loss(q, k, v, g, beta):
        o = fn(q, k, v, g, beta)
        w = jax.random.normal(jax.random.PRNGKey(7), o.shape)
        return (o * w).sum()
    return loss


@functools.cache
def _recurrence(case):
    """The per-token recurrence's output and gradients, once a case."""
    x = _inputs(CASES[case][0])
    out, grads = jax.jit(_value_and_grads(dr.recurrent_delta_rule))(*x)
    return x, out, grads


def _value_and_grads(fn):
    """``(fn(*x), its loss's gradients in all five inputs)``."""
    def both(*x):
        return fn(*x), jax.grad(_loss(fn), argnums=tuple(range(5)))(*x)
    return both


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_matches_the_recurrence(case, impl):
    """Output and the gradients in q, k, v, g and beta, each within 2e-5 of
    the largest entry of the recurrence's."""
    x, want, want_grads = _recurrence(case)
    chunk = CASES[case][1]

    def fn(*a):
        return dr.gated_delta_rule(*a, chunk=chunk, impl=impl)

    got, grads = jax.jit(_value_and_grads(fn))(*x)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    for name, a, b in zip("q k v g beta".split(), grads, want_grads):
        scale = float(jnp.abs(b).max()) + 1e-12
        assert float(jnp.abs(a - b).max()) <= 2e-5 * scale, name


def test_gates_gradient_is_exact_under_strong_decay():
    """The gate's gradient summed over positions, as ``A_log``'s is, to
    1e-4 of the recurrence's in every head, the one decaying by about -20
    a position too: the chunk form takes each sum over the gates it spans,
    where a difference of cumulative sums left about 1e-3 (the pairs on
    one side of a position cancel, and are far larger)."""
    (q, k, v, g, beta), _, want = _recurrence("padded")
    want_dg = want[3]
    for impl in ("jnp", "pallas"):
        dg = jax.jit(jax.grad(
            _loss(lambda *a: dr.gated_delta_rule(*a, chunk=16, impl=impl)),
            argnums=3,
        ))(q, k, v, g, beta)
        a_log = (dg * g).sum((0, 1))  # d/dA_log of g = -exp(A_log) * softplus
        b_log = (want_dg * g).sum((0, 1))
        assert (np.abs(a_log - b_log) <= 1e-4 * np.abs(b_log)).all(), impl


def test_kernels_agree_with_the_jnp_path_in_bfloat16():
    """bfloat16 q, k and v: the result in v's dtype, and the two paths
    apart by bfloat16's rounding of the result alone."""
    q, k, v, g, beta = _inputs(64)
    q, k, v = (x.astype(jnp.bfloat16) for x in (q, k, v))
    a = dr.gated_delta_rule(q, k, v, g, beta, chunk=32, impl="jnp")
    b = dr.gated_delta_rule(q, k, v, g, beta, chunk=32, impl="pallas")
    assert a.dtype == b.dtype == jnp.bfloat16
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    assert np.linalg.norm(a - b) / np.linalg.norm(a) < 0.01


def test_counters_and_the_kept_names():
    """One trace counts the impl, the interpreter and the chunks; the
    forward rule names its result and chunk-start states for the remat
    policy."""
    q, k, v, g, beta = _inputs(50)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    jaxpr = jax.make_jaxpr(jax.vjp, static_argnums=0)(
        lambda q: dr.gated_delta_rule(q, k, v, g, beta, chunk=16, impl="pallas"), q
    )
    c1, h1 = telemetry.counters(), telemetry.histograms()

    def rose(name):
        return c1.get(name, 0) - c0.get(name, 0)

    assert rose("delta_rule{impl=pallas}") == 1
    assert rose("delta_rule{interpret=true}") == 1
    assert h1["delta_rule.chunks"]["count"] - h0.get("delta_rule.chunks", {}).get("count", 0) == 1
    assert h1["delta_rule.chunks"]["max"] >= 4
    text = str(jaxpr)
    assert "delta_out" in text and "delta_starts" in text
    with pytest.raises(ValueError, match="multiple of 8"):
        dr.gated_delta_rule(q, k, v, g, beta, chunk=12)
