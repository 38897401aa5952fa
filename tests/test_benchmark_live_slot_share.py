"""benchmark/tests/test_live_slot_share.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_live_slot_share import *  # noqa: F401,F403
