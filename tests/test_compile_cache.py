"""The entry points' persistent compilation cache lands where it should.

Each case runs in a child process on the CPU, so the JAX settings the
helper changes never leak into the test process."""
import json
import os
import pathlib
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parents[1]

# a jitted chain long enough that its compile clears the helper's
# minimum compile time, so the cache keeps it
_SCRIPT = textwrap.dedent("""
    import json
    import jax, jax.numpy as jnp
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    if COMPILE:
        def chain(x):
            for i in range(120):
                x = jnp.sin(x) * (i + 1) + jnp.cos(x[::-1])
            return x
        jax.block_until_ready(jax.jit(chain)(jnp.arange(64.0)))
    print(json.dumps({"dir": cache_dir}))
""")


def _run(compile_something: bool, **env):
    child_env = {k: v for k, v in os.environ.items()
                 if k != "JAX_COMPILATION_CACHE_DIR"}
    child_env.update(PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu",
                     **env)
    script = f"COMPILE = {compile_something}\n" + _SCRIPT
    out = subprocess.run([sys.executable, "-c", script], env=child_env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])["dir"]


def test_cache_dir_from_environment_is_used(tmp_path):
    cache = tmp_path / "cache"
    got = _run(True, JAX_COMPILATION_CACHE_DIR=str(cache))
    assert got == str(cache)
    assert cache.is_dir() and any(cache.iterdir())


def test_cache_dir_defaults_to_fixed_checkout_path():
    assert _run(False) == str(ROOT / ".jax_cache")
