"""PIMDB core: bit-sliced bulk-bitwise analytics engine (paper's contribution)."""
from . import bitslice, compile_cache, cost_model, engine, isa  # noqa: F401
from .engine import Engine, PimRelation  # noqa: F401

# Persistent XLA compilation cache: JAX_COMPILATION_CACHE_DIR if set,
# else <checkout>/.jax_cache (see core.compile_cache).
compile_cache.enable_persistent_cache()
