import os

# tests run on the single host CPU device (the 512-device override is
# exclusively for launch/dryrun.py, per the brief)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)


@pytest.fixture(autouse=True)
def _cli_leaves_compile_cache_off(monkeypatch):
    """Keep ``python -m repro.fed.run``'s ``main``, which tests call in
    process, from switching JAX's persistent compile cache on for the rest
    of the worker: a later test would then be handed a cached executable
    of the same program compiled with other op metadata (test_spans)."""
    from repro.fed import run as cli
    monkeypatch.setattr(cli, "enable_compile_cache", lambda: None)
