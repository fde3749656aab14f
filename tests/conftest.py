"""Test configuration: run JAX on a virtual 8-device CPU mesh.

This is the TPU answer to "test distributed code without a cluster"
(SURVEY.md §4): XLA fakes 8 host devices, so every sharding/collective code
path compiles and executes exactly as it would on an 8-chip slice.
Must run before anything imports jax.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

# Whatever imported jax before this file ran (making the env vars above
# too late), the config update wins as long as no backend has been
# initialized yet.
jax.config.update("jax_platforms", "cpu")

# NO persistent compilation cache in tests: the production driver keeps
# the DEFAULT cache off on CPU (experiment/driver.enable_compilation_cache)
# and no test turns it on for the session.  The gate dates from jax
# 0.4.37, whose CPU backend corrupted donated buffers when an executable
# was DESERIALIZED from the persistent cache (a donate_argnums jit over a
# replicated sharding, compiled once then re-jitted in the same process,
# died with `free(): corrupted unsorted chunks` — or silently trained on
# garbage; the root cause of once-"flaky" mid-round-resume failures).  Not
# re-verified on the installed 0.9.0, so the gate stays.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(autouse=True)
def _no_leaked_preemption():
    """A recorded SIGTERM is process state (``faults/preempt.py``): a test
    that stops its service that way and ends must not preempt the fit of
    whichever test the worker runs next."""
    yield
    from active_learning_tpu.faults import preempt as preempt_lib
    preempt_lib.reset()


@pytest.fixture
def collective_lock(tmp_path_factory):
    """At most one of the eight-device CPU collective tests at a time,
    across the xdist workers of one run: each keeps eight device threads
    in a rendezvous that aborts the worker (``Fatal Python error:
    Aborted``) when they are starved, and two of them beside four busy
    workers starve each other.  The lock is a file in the run's own
    temporary root, which every worker shares; ``xdist_group`` would not
    do, since it has no effect under ``--dist load``."""
    import fcntl

    root = tmp_path_factory.getbasetemp()
    if os.environ.get("PYTEST_XDIST_WORKER"):
        root = root.parent
    with open(root / "collective.lock", "w") as fh:
        fcntl.flock(fh, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(fh, fcntl.LOCK_UN)
