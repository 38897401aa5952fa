"""benchmark/tests/test_looped_readers.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_looped_readers import *  # noqa: F401,F403
