"""benchmark/tests/test_controls_by_layer.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_controls_by_layer import *  # noqa: F401,F403
