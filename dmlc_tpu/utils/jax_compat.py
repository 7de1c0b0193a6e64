"""What this package asks of the installed jax beyond its API: where
compiled programs are kept between processes.

Written for the one installation there is (jax/jaxlib 0.9.0):
``jax.shard_map``, ``jax.lax.axis_size`` and ``jax.lax.pcast`` are
imported from their real homes at the use sites, with no version shims.

Compiling is seconds per shape on a TPU and every process starts cold, so
the persistent compilation cache is on for every entry point that imports
the jitting parts of the package (``dmlc_tpu.ops``, and anything built on
``obs.instrumented_jit``: the learners, the device collectives, bench,
the examples, the tools). The directory is part of the cache key, so it
must not move between runs: never a tempfile, pid or time-derived path.
"""

from __future__ import annotations

import os

import jax

#: ``<checkout>/.jax_cache`` (gitignored) — used when the environment
#: names no directory
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def place_compile_cache() -> str:
    """Decide where jax's persistent compilation cache lives; returns the
    directory. With ``JAX_COMPILATION_CACHE_DIR`` set, jax's own handling
    of it is all there is and nothing is set here; unset, the cache goes
    to :data:`REPO_CACHE_DIR`. Idempotent and cheap (one config read), so
    it is called wherever the package is about to compile."""
    if jax.config.jax_compilation_cache_dir is None:
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return jax.config.jax_compilation_cache_dir


__all__ = ["REPO_CACHE_DIR", "place_compile_cache"]
