"""benchmark/tests/test_reduce.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_reduce import *  # noqa: F401,F403
