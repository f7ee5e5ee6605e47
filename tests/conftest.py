"""Test env: virtual 8-device CPU mesh (must run before jax backend init).

Mirrors the reference's test ladder (SURVEY.md §4): a world-of-size-N on one
box — the reference uses ``mpirun -np N``; we use XLA's forced host platform
device count so the same sharded code paths compile and execute as on an
8-chip TPU slice.
"""

import os

# The environment is the one way to choose a device (README "Start-up"):
# tests, and every process they spawn, run on CPU.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags +
                               " --xla_force_host_platform_device_count=8")
# Tier-1 leaves no compile cache in the checkout (the package would put one
# at <checkout>/.jax_cache); tests that check the cache turn it back on.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def clean_framework_state():
    """Reset flags/zoo/dashboard between tests (the reference re-creates its
    MultiversoEnv fixture per suite, Test/unittests/multiverso_env.h:9-29)."""
    yield
    from multiverso_tpu.core.zoo import Zoo
    from multiverso_tpu.telemetry import reset_telemetry
    from multiverso_tpu.utils.configure import reset_flags
    from multiverso_tpu.utils.dashboard import Dashboard

    zoo = Zoo._instance
    if zoo is not None and zoo.started:
        try:
            zoo.stop()
        except Exception:
            pass
    Zoo._reset_for_tests()
    reset_flags()
    Dashboard.reset()
    reset_telemetry()   # registry + span buffer + exporter (monitors'
    # backing histograms live in the telemetry registry)


@pytest.fixture
def mv_env():
    """MultiversoEnv analog: init with default flags, world size 1."""
    import multiverso_tpu as mv
    mv.init([])
    yield mv
    mv.shutdown()


@pytest.fixture
def sync_env():
    """SyncMultiversoEnv analog (-sync=true)."""
    import multiverso_tpu as mv
    mv.init(["-sync=true"])
    yield mv
    mv.shutdown()
