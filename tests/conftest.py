import os
import sys

# Make src/ importable without install; keep the default single CPU device
# (the multi-device system test sets its own device count in a subprocess).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import pytest

# Hypothesis profiles: CI's nightly job exports HYPOTHESIS_PROFILE=nightly.
# Tests that pin explicit @settings raise their counts by reading the env
# var themselves (pins override profiles); this registration covers any
# future unpinned @given property and keeps newer hypothesis versions (which
# auto-load the profile named by the env var) from failing on an
# unregistered name.
try:
    from hypothesis import settings as _hsettings

    _hsettings.register_profile("nightly", max_examples=100, deadline=None)
    try:
        if os.environ.get("HYPOTHESIS_PROFILE"):
            _hsettings.load_profile(os.environ["HYPOTHESIS_PROFILE"])
    except Exception:
        # an unregistered profile name from the developer's shell must not
        # fail collection of the whole suite — keep the default profile
        pass
except ImportError:
    pass


def pytest_addoption(parser):
    """Register the `cov_ratchet` ini key (nightly coverage floor).

    The value itself is consumed by CI's nightly job, which greps it out of
    pytest.ini and passes it as --cov-fail-under; registering it here keeps
    local pytest runs from warning about an unknown ini option.
    """
    parser.addini(
        "cov_ratchet",
        "nightly coverage ratchet percentage (source of --cov-fail-under)",
        default="0",
    )


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def pytest_collection_modifyitems(config, items):
    """Auto-skip @pytest.mark.tpu tests when no TPU backend is present."""
    import jax

    if jax.default_backend() == "tpu":
        return
    skip_tpu = pytest.mark.skip(reason="requires a TPU backend")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)
