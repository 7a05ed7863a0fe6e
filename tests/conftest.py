"""Test harness: 16 virtual CPU devices — the JAX analog of the reference's
"multi-node on one box" (mp.spawn + Gloo over localhost, SURVEY §4).
16 (up from 8) so the full 4-axis sharding composition
(node × seq × model × expert, 2 each) runs in the default suite.

Must run before any JAX backend initialization: every test runs on the
CPU backend, whatever accelerator the host has.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=16 "
    + os.environ.get("XLA_FLAGS", "")
)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _session_compile_cache(tmp_path_factory):
    """``fit`` and the server keep a persistent compile cache by default,
    inside the checkout. The suite places its own, one per session, so a
    run neither reads what an earlier run compiled nor leaves files in
    the tree (a cache the program placed is kept by later default
    calls: ``programs.resolve_cache_dir``)."""
    from gym_tpu.programs import enable_disk_tier
    enable_disk_tier(str(tmp_path_factory.mktemp("jax_cache")),
                     min_compile_time_secs=None)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8, f"expected 8 cpu devices, got {len(devs)}"
    return devs[:8]


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
