"""Test configuration.

Distributed tests run on a virtual multi-device CPU mesh — the JAX analog of
the reference's multi-process FSDPTest harness (see SURVEY.md §4).

Tests always run on 8 virtual CPU devices, whatever the machine holds: the
device count is forced through ``XLA_FLAGS`` and the platform through
``jax.config`` (which also wins over a ``JAX_PLATFORMS`` naming a chip).  The
chip is reached only by ``python chip_smoke.py`` (README, "Build / test").

JAX itself is optional: the torch-only surface (fake tensors, deferred init,
torch materialization) must stay testable in a JAX-less environment, so the
import is guarded and JAX-dependent test modules skip via their own imports.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

try:
    import jax
except ImportError:
    jax = None

if jax is not None:
    jax.config.update("jax_platforms", "cpu")
else:
    # torch-only environment: skip collection of JAX-dependent modules so
    # the torch-surface tests (fake, deferred init, native tape) still run.
    collect_ignore = [
        "test_attention.py",
        "test_checkpoint.py",
        "test_gpt2.py",
        "test_materialize_jax.py",
        "test_models.py",
        "test_sharding_plans.py",
        "test_slowmo.py",
        "test_trace_report.py",
        "test_train_step.py",
    ]
