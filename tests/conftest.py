"""Shared fixtures. NOTE: no XLA_FLAGS here on purpose — smoke tests and
benches must see the real (single) device; only launch/dryrun.py forces 512
host devices."""
import importlib.util
import os
import pathlib

import jax
import pytest

# Property-based modules need hypothesis (see requirements-dev.txt).  When it
# is absent, skip those modules at collection instead of erroring the run.
if importlib.util.find_spec("hypothesis") is None:
    collect_ignore = sorted(
        p.name for p in pathlib.Path(__file__).parent.glob("test_*.py")
        if any(line.startswith(("import hypothesis", "from hypothesis"))
               for line in p.read_text().splitlines()))
elif os.environ.get("CI"):
    # derandomized draws on CI: every run sees the same examples, so a CI
    # failure reproduces locally instead of hiding behind a random draw —
    # no plugin flags needed
    from hypothesis import settings

    settings.register_profile("ci", derandomize=True, deadline=None)
    settings.load_profile("ci")


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
