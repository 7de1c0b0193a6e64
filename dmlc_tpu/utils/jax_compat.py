"""What this package asks of the installed jax beyond its API: where
compiled programs are kept between processes, and how Pallas is imported
(without the GPU's stack, its bytecode kept beside those programs).

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

import importlib
import os
import sys
import threading

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


#: the interpreter of GPU Mosaic kernels, which ``jax._src.pallas.
#: pallas_call`` imports at its foot inside a ``try: ... except
#: ImportError:`` of its own that binds a stand-in: two thirds of what
#: ``import jax.experimental.pallas`` costs (``jax._src.pallas.mosaic_gpu.
#: core``, jaxlib's llvm / nvvm / gpu dialects, ``jax.experimental.mosaic.
#: gpu``: 45 modules), none of which a TPU process calls (PERF.md, PR 41)
_GPU_INTERPRETER = "jax._src.pallas.mosaic_gpu.interpret"

_PALLAS = ("jax.experimental.pallas", "jax.experimental.pallas.tpu")


#: one import at a time: what :func:`import_pallas` sets for the import's
#: duration is the interpreter's own, shared by every thread
_IMPORT_LOCK = threading.RLock()


def import_pallas():
    """``(pl, pltpu)``: ``jax.experimental.pallas`` and its ``tpu``
    module, the one place this package imports them from, so that no
    order of imports brings the GPU's Mosaic stack in. The first call of
    a process pays the import (on the chip's host 1.16 s plain and 0.46
    s as narrowed here; with the bytecode kept, PERF.md, PR 41, has what
    was measured). ``ops/pallas_kernels.py`` calls it when that module is
    imported and a one-chip FM learner when it has made a tree whose rows
    the DMA writer will write; importing ``dmlc_tpu.models`` calls
    nothing.

    For the duration of the import :data:`_GPU_INTERPRETER` is made
    unimportable (``sys.modules[name] = None``: an ``import`` of it
    raises), which jax's own ``except ImportError`` provides for. Not
    done in a process that has imported Pallas (or that module) already.
    Where jax's layout differs (the import fails with the name blocked;
    a name that does not exist blocks nothing) this falls back to the
    plain import, and the process is merely slower to start.

    Two thirds of what is left is Python compiling Pallas' modules from
    source, which an installation that keeps no bytecode
    (``PYTHONDONTWRITEBYTECODE``, no ``__pycache__``) repeats in every
    process. Under such an installation their bytecode is kept under
    :data:`REPO_CACHE_DIR`, beside the compiled programs and nowhere
    about the installation, for the duration of this import only
    (``sys.pycache_prefix``; stale files are Python's own to detect, a
    directory that cannot be written costs nothing).

    What is set here (an entry of ``sys.modules``, ``sys.pycache_prefix``,
    ``sys.dont_write_bytecode``) belongs to the whole process: callers
    take :data:`_IMPORT_LOCK`, so one thread at a time sets and restores
    it, and everything is as it was before this returns, whatever
    happened. A thread that imports something else of its own in that
    quarter second does so under the same settings (its bytecode lands
    under the same directory; its import of the GPU's interpreter, which
    nothing of this package makes, would raise ``ImportError``)."""
    with _IMPORT_LOCK:
        if (_PALLAS[0] not in sys.modules
                and _GPU_INTERPRETER not in sys.modules):
            sys.modules[_GPU_INTERPRETER] = None
            bytecode = sys.pycache_prefix, sys.dont_write_bytecode
            if bytecode == (None, True):
                sys.pycache_prefix = os.path.join(REPO_CACHE_DIR, "pycache")
                sys.dont_write_bytecode = False
            try:
                for name in _PALLAS:
                    importlib.import_module(name)
            except ImportError:
                # the modules that failed are out of ``sys.modules``
                # again, and the plain import below runs them anew
                pass
            finally:
                sys.pycache_prefix, sys.dont_write_bytecode = bytecode
                sys.modules.pop(_GPU_INTERPRETER, None)
        pl, pltpu = (importlib.import_module(name) for name in _PALLAS)
    return pl, pltpu


__all__ = ["REPO_CACHE_DIR", "import_pallas", "place_compile_cache"]
