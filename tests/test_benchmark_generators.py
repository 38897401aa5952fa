"""benchmark/tests/test_generators.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_generators import *  # noqa: F401,F403
