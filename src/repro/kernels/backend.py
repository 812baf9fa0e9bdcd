"""Backend dispatch for the Pallas kernels: compiled on TPU, interpreted
elsewhere, detected once per process.

The kernel entry points (``composite_fwd``, ``grad_mag_fwd``,
``flash_attention_fwd``, ``ssd_scan_fwd``) historically defaulted to
``interpret=True`` unconditionally — correct everywhere, but it silently
pays the Pallas interpreter cost on real TPU hardware (the §V.C kernels
exist precisely to be fast there).  :func:`resolve_interpret` is the one
place that decision lives now: ``interpret=None`` (the new default) means
"detect the backend"; an explicit ``True``/``False`` always wins (tests
pin ``True`` for the CPU correctness sweeps; a TPU debugging session can
force ``True`` to use the interpreter, cf. ``pltpu.force_tpu_interpret_mode``).

Two more backend facts live here: the block rows the chip's tiling wants
(:func:`row_block`) and where compiled programs are cached
(:func:`enable_compile_cache`).
"""

from __future__ import annotations

import functools
import os
import pathlib

import jax
import jax.numpy as jnp

#: where the persistent compilation cache goes when the environment names
#: none: fixed, inside the checkout, so each run finds what the last compiled
_REPO_CACHE = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    """True when the default JAX backend is a TPU (cached: backend choice
    is fixed for the life of the process)."""
    return jax.default_backend() == "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """Map the tri-state ``interpret`` argument to a concrete mode:
    None -> compiled on TPU / interpreted elsewhere; bool -> as given."""
    return (not on_tpu()) if interpret is None else interpret


def row_block(h: int, block_h: int, *dtypes) -> int:
    """Rows per block of an ``[..., H, W]`` operand: ``block_h`` rounded up
    to the sublane tile of the narrowest dtype (8 rows of a 32-bit type,
    16 of a 16-bit one), so that a block fills whole native (sublane,
    lane) tiles of every operand.  Where that block does not divide ``h``,
    one block spans all ``h`` rows, which the chip always accepts."""
    tile = 32 // min(jnp.dtype(d).itemsize for d in dtypes)
    block_h = -(-block_h // tile) * tile
    return block_h if block_h < h and h % block_h == 0 else h


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    JAX itself reads ``JAX_COMPILATION_CACHE_DIR`` where it is set, and then
    no other directory is set here; otherwise the cache is ``<repo>/.jax_cache``.
    Every compile is kept, however short: a cold chip run compiles each
    kernel and jitted stage once.  Call from an entry point, never while a
    module is imported."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(_REPO_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
