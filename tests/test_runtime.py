"""The persistent compilation cache an entry point turns on
(`repro.kernels.runtime.use_compilation_cache`), each case in a fresh
process."""
import os

from tests._subproc import run_py


def test_compilation_cache_uses_env_dir(tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: the helper keeps it, sets no
    other directory, and compiled entries land there."""
    out = run_py(f"""
import os
os.environ["JAX_COMPILATION_CACHE_DIR"] = {str(tmp_path)!r}
import jax, jax.numpy as jnp
from repro.kernels.runtime import use_compilation_cache
assert use_compilation_cache() == {str(tmp_path)!r}
assert jax.config.jax_compilation_cache_dir == {str(tmp_path)!r}
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)).block_until_ready()
print("ENTRIES", len(os.listdir({str(tmp_path)!r})))
""", devices=1)
    assert int(out.split("ENTRIES")[1]) > 0


def test_compilation_cache_defaults_to_checkout():
    """Unset: the cache is the fixed ``<checkout>/.jax_cache``, never a
    temp, pid- or time-derived path."""
    out = run_py("""
import os
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
import jax
from repro.kernels.runtime import CHECKOUT, use_compilation_cache
path = use_compilation_cache()
assert path == str(CHECKOUT / ".jax_cache") == jax.config.jax_compilation_cache_dir
assert (CHECKOUT / "chip_smoke.py").exists()
print("CACHE", path)
""", devices=1)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert out.split("CACHE")[1].strip() == os.path.join(root, ".jax_cache")
