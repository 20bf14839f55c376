"""The entry points' persistent compilation cache: JAX_COMPILATION_CACHE_DIR
wins when set, otherwise a fixed `<checkout>/.jax_cache`. Each case runs in
a fresh process, since the cache directory is process-wide jax state."""
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))


def _run(code: str, env_dir: str | None) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout.strip().splitlines()[-1]


def test_env_dir_is_used_and_receives_entries(tmp_path):
    d = str(tmp_path / "cache")
    out = _run("""
    import jax, jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    used = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == used
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sort(x) * 2)(jnp.arange(64)).block_until_ready()
    print(used)
    """, d)
    assert out == d
    assert os.listdir(d), "no cache entry written to JAX_COMPILATION_CACHE_DIR"


def test_default_dir_is_fixed_in_the_checkout():
    out = _run("""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    used = enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == used
    print(used)
    """, None)
    assert out == os.path.join(ROOT, ".jax_cache")
