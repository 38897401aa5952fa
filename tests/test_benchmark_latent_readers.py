"""benchmark/tests/test_latent_readers.py's cases, imported so that tier-1
(`pytest tests/`) runs them; tests/conftest.py puts that directory on the
path. Nothing is copied: edit them there."""
from test_latent_readers import *  # noqa: F401,F403
