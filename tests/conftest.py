"""Test config: force an 8-device virtual CPU platform.

This is the TPU analog of the reference's fake-cluster trick
(``scripts/tests/run-integration-tests.sh`` runs N processes on localhost):
we test all sharding/collective paths on N virtual CPU devices.

The platform is set through jax.config, which holds whatever the
environment's JAX_PLATFORMS says and however early jax was imported.
XLA_FLAGS is read at first backend init, which has not happened yet.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()

import jax

jax.config.update("jax_platforms", "cpu")
# The TPU interpreter (``pltpu.force_tpu_interpret_mode``, ``interpret=``
# with ``InterpretParams``) runs a kernel's copies as callbacks that
# dispatch JAX operations of their own.  Under the CPU's asynchronous
# dispatch a test that issues its next operation while an interpreted
# kernel is in flight queues it AHEAD of the callbacks' and behind the
# kernel: each waits for the other, and the worker sits in a futex until
# the run is cut (seen three times on six and on three workers, PR 46:
# PERF.md section 7, item 0j iv; ROADMAP D12 b).  Computations run inline
# instead; read when the backend starts, which has not happened yet.
jax.config.update("jax_cpu_enable_async_dispatch", False)

import pytest


@pytest.fixture(autouse=True)
def _reload_launch_knobs():
    """Launch-set knobs (KF_TPU_XENT, KF_PALLAS_COLLECTIVES, ...) are
    read at import, not at trace time (recompile-hazard hoist): tests
    that monkeypatch them call ``.reload()`` themselves; this teardown
    re-reads the restored environment through the shared registry so a
    mutation can never leak into the next test."""
    yield
    import kungfu_tpu.ops.pallas  # noqa: F401 — registers its knobs
    from kungfu_tpu.utils.envs import reload_launch_knobs

    reload_launch_knobs()
