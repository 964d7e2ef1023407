"""JAX/TPU kernels: GF(2^255-19) field arithmetic and batched ed25519 verify.

Importing this package enables JAX's persistent compilation cache, so a
bucket of the verify kernel compiles once per machine, not once per
process.  Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it
and nothing is set here; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache`` (the path is part of the cache key, so it must
not move between processes).
"""

import os

import jax

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    os.makedirs(DEFAULT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
