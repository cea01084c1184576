"""Small shared utilities used across the framework."""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np


# JAX's persistent compilation cache, unless JAX_COMPILATION_CACHE_DIR
# places it elsewhere: one fixed directory in the checkout (git-ignored), so
# a later run from the same checkout finds what an earlier one compiled.
COMPILATION_CACHE_DIR = os.path.abspath(os.path.join(
    os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has read it already and
    this leaves the setting alone.  Entry points call this; library code
    and tests do not.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILATION_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


def cdiv(a: int, b: int) -> int:
    """Ceiling division."""
    if b <= 0:
        raise ValueError(f"cdiv divisor must be positive, got {b}")
    return -(-a // b)


def round_up(a: int, b: int) -> int:
    """Round ``a`` up to the nearest multiple of ``b``."""
    return cdiv(a, b) * b


def tree_size(tree) -> int:
    """Total number of elements across all leaves of a pytree."""
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))


def tree_bytes(tree) -> int:
    """Total bytes across all leaves (works on ShapeDtypeStruct too)."""
    total = 0
    for x in jax.tree.leaves(tree):
        total += int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
    return total


def human_bytes(n: float) -> str:
    for unit in ("B", "KiB", "MiB", "GiB", "TiB", "PiB"):
        if abs(n) < 1024.0:
            return f"{n:.2f} {unit}"
        n /= 1024.0
    return f"{n:.2f} EiB"


def human_number(n: float) -> str:
    for unit in ("", "K", "M", "B", "T"):
        if abs(n) < 1000.0:
            return f"{n:.2f}{unit}"
        n /= 1000.0
    return f"{n:.2f}Q"
