"""What the family test files share: a configuration file's ``tiny`` sizes,
seeded tokens, the plain float32 reference's loss, that loss and its
gradient under one ``jax.jit``, and the comparison that holds a family's
loss and gradients to the reference's.

Importing this module puts ``benchmarks/`` on ``sys.path``, so that a test
file can import ``families`` and ``reference`` after it.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import common  # noqa: E402


def published(config):
    """``benchmarks/configs/<config>.json`` as the file has it."""
    with open(os.path.join(BENCH, "configs", f"{config}.json")) as f:
        return json.load(f)


def sizes(config, **over):
    """The configuration file's ``tiny`` block over its published keys."""
    c = published(config)
    c.update(c.pop("tiny"))
    c.update(over)
    return c


def tokens(sizes, shape, seed=1):
    """Seeded ``(tokens, targets)`` of ``shape``, the targets the tokens
    moved on by one."""
    ids = np.random.default_rng(seed).integers(
        0, sizes["vocab_size"], size=(shape[0], shape[1] + 1)
    )
    return jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])


def ref_loss(ref, params, tokens, targets, sizes):
    """The plain reference's loss, every product in float32."""
    with common.precision(jnp.float32):
        x = ref.hidden(params, tokens, sizes, jnp.float32)
        return common.cross_entropy(ref.head(params, x, jnp.float32), targets)


def reference(ref, sizes):
    """The plain reference's ``(loss, grads)`` as a function of ``(params,
    tokens, targets)``, under one ``jax.jit`` with ``sizes`` closed over:
    traced and compiled once a shape of tokens."""
    return jax.jit(jax.value_and_grad(
        lambda params, tokens, targets: ref_loss(ref, params, tokens, targets, sizes)
    ))


def reference_at(ref, sizes, params):
    """The plain reference's ``(loss, grads)`` at ``params`` on
    ``tokens(sizes, shape)``, as a function of ``shape`` that evaluates
    each shape once."""
    grad = reference(ref, sizes)
    return functools.cache(lambda shape: grad(params, *tokens(sizes, shape)))


def assert_matches_reference(loss, grads, want, want_grads):
    """The loss within 1e-5 of the reference's, and every gradient leaf
    within 2e-4 of its largest entry (plus 1e-7); the leaf's path in the
    message."""
    assert abs(float(loss) - float(want)) < 1e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads), strict=True):
        scale = float(jnp.abs(w).max()) + 1e-8
        assert float(jnp.abs(g - w).max()) <= 2e-4 * scale + 1e-7, path
