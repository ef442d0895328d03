"""Where XLA's persistent compile cache lives: placed from outside.

A chip run starts with no compiled code and compiling is most of a cold
run, so every entry point that compiles calls `enable()` before its first
jit. The directory is part of the cache key, so it must not move:

- `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself; nothing here (or
  anywhere in this repo) sets another directory.
- unset: `<checkout>/.jax_cache` (git-ignored) — never a temp name, a pid
  or a time.
"""
import os
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parent.parent


def enable() -> str:
    """Turn the persistent cache on (idempotent); returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # The default floor (1 s of compile time) skips exactly the small
    # kernels this repo is made of.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def entry_count(path: str) -> int:
    """Cached executables under `path` (0 if it does not exist yet)."""
    try:
        return sum(1 for name in os.listdir(path) if name.endswith("-cache"))
    except FileNotFoundError:
        return 0
