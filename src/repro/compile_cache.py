"""JAX's persistent compilation cache: one directory, chosen in one place.

A full-size train or serve program takes minutes to compile on the chip.
The cache keeps the compiled programs on disk in a directory that does
not move between runs, so a later run finds what an earlier one wrote.
Every entry
point that compiles (the measuring commands of ``python -m repro`` and
its sub-CLIs, ``repro.launch.train``, ``repro.launch.serve`` and
``chip_smoke.py``) calls :func:`enable_compile_cache` before its first
compile.  The commands that only read the stores do not: asking for the
backend would claim the chip from the process that measures on it.
"""

from __future__ import annotations

import os

#: JAX reads this variable itself; where it is set, nothing else is set
ENV = "JAX_COMPILATION_CACHE_DIR"

#: the checkout's own cache directory (listed in ``.gitignore``)
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax-cache")


def enable_compile_cache() -> str | None:
    """Turn the persistent compilation cache on; return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_DIR`.

    On the CPU backend nothing is turned on (``None``): CPU programs
    compile in seconds, and XLA:CPU warns that a cached executable may
    not match the host's machine features.  Asking for the backend
    initialises it, which on an accelerator claims the chip.
    """
    path = os.environ.get(ENV)
    if path:
        return path
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
