"""benchmark/tests/test_correct_faults.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_correct_faults import *  # noqa: F401,F403
