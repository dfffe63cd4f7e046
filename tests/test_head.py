"""The row-blocked loss head (``models/_common.blocked_head_ce``), which
takes its gradient in the forward pass: against the whole-logits head
(``llama._ce`` of ``h @ W``) and against the form it replaced, a scan of
``jax.checkpoint``ed blocks whose backward replayed the logits product.
Tied (``(V, D)``, ``vocab_major``) and untied (``(D, V)``), one block and
four.

CPU, seeded: values, jaxprs and counts only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchdistx_tpu import telemetry
from torchdistx_tpu.models import _common
from torchdistx_tpu.models import llama as llama_mod

N, D, V = 128, 64, 320
BLOCKS = pytest.mark.parametrize("rows", [N, N // 4], ids=["one_block", "four_blocks"])
LAYOUTS = pytest.mark.parametrize("vocab_major", [False, True], ids=["untied", "tied"])
REMAT = {"checkpoint", "remat", "remat2"}  # ``jax.checkpoint``'s primitive


def _inputs(vocab_major, dtype=jnp.float32):
    kh, kw, kt = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(kh, (N, D)).astype(dtype)
    w = 0.5 * jax.random.normal(kw, (V, D) if vocab_major else (D, V))
    return h, w.astype(dtype), jax.random.randint(kt, (N,), 0, V)


def _blocked(vocab_major, t):
    return lambda h, w: _common.blocked_head_ce(h, w, t, vocab_major=vocab_major)


def _whole(vocab_major, t):
    return lambda h, w: llama_mod._ce(h @ (w.T if vocab_major else w), t)


def _remat(vocab_major, rows, t):
    """The head this one replaced (PR 37's ``smallthinker._head_ce`` /
    ``jamba._head_ce`` less the norm): the same loss, autodiff through a
    scan of rematerialised blocks."""

    def loss(h, w):
        size = rows if h.shape[0] % rows == 0 else h.shape[0]

        @jax.checkpoint
        def block(total, xs):
            hb, tb = xs
            logits = jnp.einsum("rd,vd->rv", hb, w) if vocab_major else hb @ w
            lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
            tgt = jnp.take_along_axis(logits, tb[:, None], axis=-1)[:, 0]
            return total + (lse - tgt.astype(jnp.float32)).sum(), None

        total, _ = jax.lax.scan(
            block, jnp.zeros((), jnp.float32),
            (h.reshape(-1, size, h.shape[-1]), t.reshape(-1, size)),
        )
        return total / h.shape[0]

    return loss


def _value_and_grad(f, h, w):
    return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(h, w)


@LAYOUTS
@BLOCKS
def test_the_loss_is_the_whole_heads_bit_for_bit(vocab_major, rows, monkeypatch):
    """Float32: the parent's row-blocked loss bit for bit, and at one
    block ``llama._head_ce``'s; four blocks add four partial sums, which
    the whole mean does not, so there it is within a rounding."""
    monkeypatch.setattr(_common, "_HEAD_ROWS", rows)
    h, w, t = _inputs(vocab_major)
    loss = jax.jit(_blocked(vocab_major, t))(h, w)
    assert loss == jax.jit(_remat(vocab_major, rows, t))(h, w)
    whole = jax.jit(_whole(vocab_major, t))(h, w)
    if rows == N:
        assert loss == whole
    else:
        assert abs(float(loss) - float(whole)) < 1e-6


@LAYOUTS
@BLOCKS
def test_the_gradients_are_the_whole_heads(vocab_major, rows, monkeypatch):
    monkeypatch.setattr(_common, "_HEAD_ROWS", rows)
    h, w, t = _inputs(vocab_major)
    _, got = _value_and_grad(_blocked(vocab_major, t), h, w)
    _, want = _value_and_grad(_whole(vocab_major, t), h, w)
    for a, b in zip(got, want, strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-5)


@LAYOUTS
def test_a_cotangent_other_than_one_scales_both_gradients(vocab_major, monkeypatch):
    monkeypatch.setattr(_common, "_HEAD_ROWS", N // 4)
    h, w, t = _inputs(vocab_major)
    f = _blocked(vocab_major, t)
    _, once = _value_and_grad(f, h, w)
    _, thrice = _value_and_grad(lambda h, w: 3.0 * f(h, w), h, w)
    for a, b in zip(thrice, once, strict=True):
        np.testing.assert_allclose(a, 3.0 * b, atol=1e-7, rtol=1e-6)


@LAYOUTS
@BLOCKS
def test_bf16_gradients_are_the_remat_heads(vocab_major, rows, monkeypatch):
    """The same products on the same bf16 logits' cotangent: ``dh`` bit
    for bit; ``dW`` too at one block, and past one its bf16 sum over the
    blocks runs forward where the replay's ran backward."""
    monkeypatch.setattr(_common, "_HEAD_ROWS", rows)
    h, w, t = _inputs(vocab_major, jnp.bfloat16)
    loss, (dh, dw) = _value_and_grad(_blocked(vocab_major, t), h, w)
    want, (dh_r, dw_r) = _value_and_grad(_remat(vocab_major, rows, t), h, w)
    assert loss == want
    assert dh.dtype == dw.dtype == jnp.bfloat16
    np.testing.assert_array_equal(dh, dh_r)
    if rows == N:
        np.testing.assert_array_equal(dw, dw_r)
    scale = float(jnp.abs(dw_r.astype(jnp.float32)).max())
    np.testing.assert_allclose(
        dw.astype(jnp.float32), dw_r.astype(jnp.float32),
        atol=2**-7 * scale, rtol=0,
    )


def _vocab_products(jaxpr):
    """``dot_general``s with a ``V``-wide operand or result in a jaxpr and
    every jaxpr under it, and the primitives' names seen on the way."""
    count, names = 0, set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        if eqn.primitive.name == "dot_general":
            shapes = [v.aval.shape for v in (*eqn.invars, *eqn.outvars)]
            count += any(V in s for s in shapes)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    c, n = _vocab_products(sub)
                    count, names = count + c, names | n
    return count, names


@LAYOUTS
def test_three_vocabulary_wide_products_a_block_and_no_replay(vocab_major, monkeypatch):
    """A block's scan body holds the logits product and the two that take
    its cotangent to ``h`` and the table, and nothing runs a fourth: the
    replaced head's gradient held four, one under ``checkpoint``."""
    monkeypatch.setattr(_common, "_HEAD_ROWS", N // 4)
    h, w, t = _inputs(vocab_major, jnp.bfloat16)
    c0, h0 = telemetry.counters(), telemetry.histograms()
    jaxpr = jax.make_jaxpr(
        jax.value_and_grad(_blocked(vocab_major, t), argnums=(0, 1))
    )(h, w).jaxpr
    c1, h1 = telemetry.counters(), telemetry.histograms()
    products, names = _vocab_products(jaxpr)
    assert products == 3
    assert not REMAT & names
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert scan.params["length"] == 4
    assert _vocab_products(scan.params["jaxpr"].jaxpr)[0] == 3
    key = "head.ce{grad=forward}"
    assert c1.get(key, 0) == c0.get(key, 0) + 1
    seen = h1["head.row_blocks"]
    assert seen["count"] == h0.get("head.row_blocks", {}).get("count", 0) + 1
    assert seen["max"] >= 4

    replaced = jax.make_jaxpr(
        jax.value_and_grad(_remat(vocab_major, N // 4, t), argnums=(0, 1))
    )(h, w).jaxpr
    products, names = _vocab_products(replaced)
    assert products == 4 and REMAT & names


def test_the_loss_alone_runs_no_gradient_product(monkeypatch):
    """Not differentiated, the head runs the logits product alone and
    counts no forward-rule trace."""
    monkeypatch.setattr(_common, "_HEAD_ROWS", N // 4)
    h, w, t = _inputs(False)
    c0 = telemetry.counters().get("head.ce{grad=forward}", 0)
    jaxpr = jax.make_jaxpr(_blocked(False, t))(h, w).jaxpr
    assert _vocab_products(jaxpr)[0] == 1
    assert telemetry.counters().get("head.ce{grad=forward}", 0) == c0
