"""Where the launchers and ``chip_smoke.py`` keep JAX's persistent
compilation cache: ``$JAX_COMPILATION_CACHE_DIR`` when it is set, else
``.jax_cache`` at the checkout root."""
import os
import subprocess
import sys
import textwrap

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = textwrap.dedent("""
    import sys
    sys.path.insert(0, %r)
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.arange(8.0)).block_until_ready()
    print("CACHE", path, jax.config.jax_compilation_cache_dir)
""")


def _run(env):
    out = subprocess.run([sys.executable, "-c", SCRIPT % SRC],
                         capture_output=True, text=True, timeout=300,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("CACHE")]
    return line[0].split()[1:]


def test_env_dir_wins_and_receives_entries(tmp_path):
    cache = str(tmp_path / "jcc")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache,
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    returned, configured = _run(env)
    assert returned == configured == cache
    assert os.listdir(cache), "no compiled executable was written"


def test_default_dir_is_fixed_under_checkout():
    from repro.launch.compile_cache import CHECKOUT
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["JAX_PLATFORMS"] = "cpu"
    first = _run(env)
    assert first == [str(CHECKOUT / ".jax_cache")] * 2
    assert _run(env) == first            # no pid, temp name or time in it
    assert (CHECKOUT / "chip_smoke.py").is_file()
