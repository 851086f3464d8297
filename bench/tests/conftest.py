"""Fixtures shared by the harness's CPU tests."""
import pytest


@pytest.fixture(scope="session")
def jax_cache(tmp_path_factory):
    """The compile cache of every CPU server these tests start: one of
    their own, never the chip runs' ``bench/cache/jax`` (a CPU test's
    entries there would be copied to the chip's machine with the
    tree)."""
    return str(tmp_path_factory.mktemp("jax_cache"))
