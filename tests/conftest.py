"""Shared fixtures.  NOTE: never import repro.launch.dryrun here — it
forces 512 host devices; smoke tests must see the real (1-device) CPU."""
import jax
import pytest

from repro.launch.mesh import make_local_mesh


@pytest.fixture(scope="session")
def mesh11():
    return make_local_mesh()


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
