"""Shared fixtures.  NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the real single CPU device; only launch/dryrun.py forces
512 placeholder devices (in its own process)."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# the static schedule verifier (repro.analysis) is always-on under the test
# suite: any plan a test builds is checked before a kernel sees it
os.environ.setdefault("REPRO_VERIFY", "1")

try:
    from hypothesis import settings as _hyp_settings
except ImportError:     # offline: tests fall back to tests/_propcheck
    pass
else:
    # a property test's first example traces and compiles, which takes
    # seconds: wall-clock deadlines would fail it by chance, not by bug
    _hyp_settings.register_profile("repro", deadline=None)
    _hyp_settings.load_profile("repro")


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
