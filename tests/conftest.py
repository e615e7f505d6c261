"""Shared test fixtures. The suite runs on the CPU backend, so it never
takes a chip, with the device count raised to 8 for the mesh tests (NOT
512 -- the production meshes are exercised only via the dry-run)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh3():
    """2x2x2 (pod, data, model) mesh on CPU devices."""
    from repro.launch.mesh import make_mesh
    return make_mesh((2, 2, 2), ("pod", "data", "model"))


@pytest.fixture(scope="session")
def mesh2():
    """4x2 (data, model) single-pod-style mesh."""
    from repro.launch.mesh import make_mesh
    return make_mesh((4, 2), ("data", "model"))


@pytest.fixture()
def rng():
    return np.random.default_rng(0)
