"""Where the program runs: Pallas interpret mode and the compile cache.

``pallas_interpret`` is the one place that decides whether a Pallas
kernel runs compiled or in the interpreter: compiled on the TPU,
interpreted on the CPU backend (tests, laptops), and an error anywhere
else — a kernel must never silently drop to the interpreter on an
accelerator it was not written for.

``use_compile_cache`` points JAX's persistent compilation cache at a
fixed directory of the checkout. Entry points call it (``chip_smoke.py``,
``bench/run.py``); importing ``repro`` never does, and neither do the
tests.
"""
from __future__ import annotations

import os
import pathlib

import jax

CHECKOUT = pathlib.Path(__file__).resolve().parents[2]
COMPILE_CACHE_DIR = CHECKOUT / ".jax_cache"


def pallas_interpret() -> bool:
    """True on the CPU backend, False on the TPU; any other platform
    raises (the kernels are written for the TPU and validated on the
    CPU, nothing else)."""
    platform = jax.default_backend()
    if platform == "tpu":
        return False
    if platform == "cpu":
        return True
    raise RuntimeError(f"Pallas kernels run on the TPU (compiled) or "
                       f"the CPU (interpreted), not on {platform!r}")


def use_compile_cache() -> str:
    """Enable JAX's persistent compilation cache and return its
    directory. ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own
    setting and is left alone; otherwise the cache goes to
    ``<checkout>/.jax_cache`` — a fixed path, because the path is part
    of the cache key."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
