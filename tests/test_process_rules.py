"""The two process rules of running on a chip, each checked in a fresh
interpreter: importing the package initialises no JAX backend (a process
that has initialised one holds the chip, and launcher / lint / bench
parents must leave it to the child they start), and the compile cache sits
where JAX_COMPILATION_CACHE_DIR says, or at `<checkout>/.jax_cache`."""
import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code, tmp_path, **env_over):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(PYTHONPATH=REPO, HOME=str(tmp_path), JAX_PLATFORMS="cpu",
               **env_over)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=300,
                          cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_import_initialises_no_backend(tmp_path):
    """Neither the package nor the modules a chip-free parent imports
    (launcher, lint CLI, serving, the GPT model file) touch a device."""
    code = (
        "import json\n"
        "import paddle_tpu\n"
        "import paddle_tpu.serving, paddle_tpu.models.gpt\n"
        "import paddle_tpu.distributed.launch.main\n"
        "import paddle_tpu.analysis.cli\n"
        "from jax._src import xla_bridge\n"
        "print(json.dumps(xla_bridge.backends_are_initialized()))\n"
    )
    assert _run(code, tmp_path) is False


_CACHE_DIR = (
    "import json, jax, paddle_tpu\n"
    "print(json.dumps(jax.config.jax_compilation_cache_dir))\n"
)


def test_compile_cache_follows_the_environment(tmp_path):
    want = str(tmp_path / "placed")
    assert _run(_CACHE_DIR, tmp_path, JAX_COMPILATION_CACHE_DIR=want) == want
    # and nothing was made under the (fresh) home directory
    assert not (tmp_path / ".cache").exists()


def test_compile_cache_defaults_to_the_checkout(tmp_path):
    assert _run(_CACHE_DIR, tmp_path) == os.path.join(REPO, ".jax_cache")
    assert not (tmp_path / ".cache").exists()
