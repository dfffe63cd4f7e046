"""The comparison that decides ``correct``: the program's outputs against
the plain reference of the configuration's family."""

import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np

from . import common



def load(name: str):
    """The reference module ``reference/<name>.py``, found by name."""
    return importlib.import_module(f"reference.{name}")


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype", "n_rows"))
def _rows_logits(params, tokens, first_row, *, ref, sizes, dtype, n_rows):
    with common.precision(dtype):
        x = ref.hidden(params, tokens[None], dict(sizes), dtype)[0]
        rows = jax.lax.dynamic_slice_in_dim(x, first_row, n_rows, axis=0)
        return ref.head(params, rows, dtype)


def stream_logits(ref, params, sizes, prompt, generated, width, n_rows,
                  dtype=jnp.float32):
    """Reference logits at every position that produced a generated token:
    rows ``len(prompt)-1 .. len(prompt)+len(generated)-2`` of ONE full
    forward over ``prompt + generated``, padded to ``width`` (the causal
    mask keeps the padding out) so a cell compiles one program.  Returns
    ``(len(generated), V)`` float32 on the host."""
    seq = np.concatenate([prompt, np.asarray(generated, np.int32)])
    if len(seq) > width or len(generated) > n_rows:
        raise ValueError(f"stream of {len(seq)} tokens exceeds {width}")
    first = min(len(prompt) - 1, width - n_rows)
    padded = np.zeros((width,), np.int32)
    padded[: len(seq)] = seq
    out = _rows_logits(
        params, jnp.asarray(padded), jnp.int32(first), ref=ref,
        sizes=_freeze(sizes), dtype=jnp.dtype(dtype), n_rows=n_rows,
    )
    skip = len(prompt) - 1 - first
    return np.asarray(out)[skip: skip + len(generated)]


def stream_gaps(ref, params, sizes, prompt, generated, width, n_rows,
                dtype=jnp.float32):
    """For each generated token, how far its reference logit lies below
    the reference's best at that position (0 = the reference's argmax)."""
    lg = stream_logits(
        ref, params, sizes, prompt, generated, width, n_rows, dtype
    )
    if not np.isfinite(lg).all():
        return np.full(len(generated), np.inf)
    picked = lg[np.arange(len(generated)), np.asarray(generated)]
    return lg.max(axis=-1) - picked


@functools.partial(jax.jit, static_argnames=("ref", "sizes", "dtype"))
def _loss(params, tokens, targets, *, ref, sizes, dtype):
    with common.precision(dtype):
        x = ref.hidden(params, tokens, dict(sizes), dtype)
        return common.cross_entropy(ref.head(params, x, dtype), targets)


def loss(ref, params, sizes, tokens, targets, dtype=jnp.float32) -> float:
    return float(
        _loss(
            params, jnp.asarray(tokens), jnp.asarray(targets), ref=ref,
            sizes=_freeze(sizes), dtype=jnp.dtype(dtype),
        )
    )


def _freeze(sizes: dict):
    """The numbers of a configuration as a hashable static argument."""
    return tuple(
        sorted(
            (k, v) for k, v in sizes.items()
            if isinstance(v, (int, float)) and not isinstance(v, bool)
        )
    )
