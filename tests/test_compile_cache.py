"""The one compile-cache switch: placed from outside, or one fixed path."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = (
    "import jax\n"
    "from hpbandster_tpu.utils.compile_cache import "
    "enable_persistent_compile_cache\n"
    "before = jax.config.jax_compilation_cache_dir\n"
    "used = enable_persistent_compile_cache()\n"
    "import json\n"
    "print(json.dumps([before, used, jax.config.jax_compilation_cache_dir, "
    "jax.config.jax_persistent_cache_min_compile_time_secs]))\n"
)


def _probe(env_dir, cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("HPB_XLA_CACHE", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c", _PROBE], env=env, cwd=cwd,
        capture_output=True, text=True, timeout=120, check=True,
    ).stdout
    return json.loads(out.strip().splitlines()[-1])


def test_env_directory_is_used_and_none_is_set_in_code(tmp_path):
    placed = str(tmp_path / "placed-from-outside")
    before, used, after, threshold = _probe(placed, cwd=str(tmp_path))
    # jax read the directory from its own environment variable; the
    # switch reports it and leaves the config value untouched
    assert before == placed and after == placed and used == placed
    # the threshold is still set in code, so jax caches EVERY program there
    assert threshold == 0.0


def test_default_is_one_fixed_in_checkout_path(tmp_path):
    first = _probe(None, cwd=str(tmp_path))
    second = _probe(None, cwd=REPO)  # another process, another cwd
    expected = os.path.join(REPO, ".jax_compilation_cache")
    assert first[0] is None  # nothing set until the switch runs
    assert first[1] == first[2] == expected
    assert second[1:] == first[1:]
    # never committed
    with open(os.path.join(REPO, ".gitignore")) as fh:
        assert ".jax_compilation_cache/" in fh.read().split()
