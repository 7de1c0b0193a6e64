"""``dmlc_tpu/utils/jax_compat.py`` ``import_pallas``: the one place the
package imports ``jax.experimental.pallas`` from, with the GPU's Mosaic
interpreter (two thirds of the import, nothing a TPU or CPU process
calls) kept out for the duration of the import. Each case runs in a
process of its own: whether Pallas is imported is a fact of the process,
and the suite's other Pallas tests must not decide the outcome."""

import os
import re
import subprocess
import sys
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
GPU_STACK = ("jax._src.pallas.mosaic_gpu.interpret",
             "jax._src.pallas.mosaic_gpu.core", "jax.experimental.mosaic.gpu")


def _run(body: str, **environ) -> str:
    code = "import sys\nsys.path.insert(0, %r)\n" % ROOT + textwrap.dedent(body)
    env = dict(os.environ, JAX_PLATFORMS="cpu", **environ)
    for name in [k for k, v in environ.items() if v is None]:
        del env[name]
    done = subprocess.run([sys.executable, "-c", code], env=env, timeout=300,
                          capture_output=True, text=True)
    assert done.returncode == 0, done.stderr[-4000:]
    return done.stdout


def test_the_import_returns_working_modules_and_leaves_the_gpu_stack_out():
    out = _run("""
        import jax, jax.numpy as jnp
        from dmlc_tpu.utils import jax_compat
        assert "jax.experimental.pallas" not in sys.modules
        pl, pltpu = jax_compat.import_pallas()
        assert pl is sys.modules["jax.experimental.pallas"]
        assert pltpu is sys.modules["jax.experimental.pallas.tpu"]
        for name in %r:
            # neither imported nor blocked (a blocking entry is None)
            assert name not in sys.modules, name
        # a kernel runs: the modules work
        def double(x_ref, o_ref):
            o_ref[...] = x_ref[...] * 2.0
        x = jnp.arange(8 * 128, dtype=jnp.float32).reshape(8, 128)
        got = pl.pallas_call(
            double, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=True)(x)
        assert bool((got == 2.0 * x).all())
        assert pltpu.PrefetchScalarGridSpec and pltpu.make_async_copy
        # again: the same modules, nothing imported anew
        before = len(sys.modules)
        assert jax_compat.import_pallas() == (pl, pltpu)
        assert len(sys.modules) == before
        # and the name left unblocked imports when somebody asks for it
        import jax._src.pallas.mosaic_gpu.interpret
        print("ok")
    """ % (GPU_STACK,))
    assert out.strip().endswith("ok")


def test_a_name_that_does_not_exist_blocks_nothing():
    """jax laid out otherwise: the function still returns (the plain
    import), and its blocking entry is gone."""
    out = _run("""
        from dmlc_tpu.utils import jax_compat
        jax_compat._GPU_INTERPRETER = "jax._src.pallas.no_such_module"
        pl, pltpu = jax_compat.import_pallas()
        assert pl.pallas_call and pltpu.make_async_copy
        assert "jax._src.pallas.no_such_module" not in sys.modules
        assert "jax._src.pallas.mosaic_gpu.interpret" in sys.modules
        print("ok")
    """)
    assert out.strip().endswith("ok")


def test_an_import_that_fails_when_blocked_falls_back_to_the_plain_one():
    """Blocking a module Pallas cannot do without makes the narrowed
    import raise; the function undoes the block and imports plainly."""
    out = _run("""
        from dmlc_tpu.utils import jax_compat
        jax_compat._GPU_INTERPRETER = "jax._src.pallas.primitives"
        pl, pltpu = jax_compat.import_pallas()
        assert pl.pallas_call and pltpu.make_async_copy
        assert sys.modules["jax._src.pallas.primitives"] is not None
        print("ok")
    """)
    assert out.strip().endswith("ok")


def test_a_process_that_imported_pallas_is_left_alone():
    out = _run("""
        import jax.experimental.pallas as pl
        from dmlc_tpu.utils import jax_compat
        interpreter = sys.modules["jax._src.pallas.mosaic_gpu.interpret"]
        got, _ = jax_compat.import_pallas()
        assert got is pl
        # what the plain import brought stays as it was: nothing blocked
        assert sys.modules["jax._src.pallas.mosaic_gpu.interpret"] is interpreter
        assert None not in sys.modules.values()
        print("ok")
    """)
    assert out.strip().endswith("ok")


def test_threads_that_import_at_once_take_turns():
    """What the function sets is the whole process's (``sys.modules``,
    the bytecode switches): two threads that both find Pallas not yet
    imported must not both set and both restore."""
    out = _run("""
        import threading
        from dmlc_tpu.utils import jax_compat
        before = sys.pycache_prefix, sys.dont_write_bytecode
        gate = threading.Barrier(4)
        got, failed = [], []
        def work():
            gate.wait()
            try:
                got.append(jax_compat.import_pallas())
            except BaseException as e:
                failed.append(repr(e))
        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert failed == [], failed
        assert len(got) == 4 and all(pair == got[0] for pair in got)
        assert (sys.pycache_prefix, sys.dont_write_bytecode) == before
        assert None not in sys.modules.values()
        assert "jax._src.pallas.mosaic_gpu.interpret" not in sys.modules
        print("ok")
    """)
    assert out.strip().endswith("ok")


KEPT = """
    from dmlc_tpu.utils import jax_compat
    jax_compat.REPO_CACHE_DIR = %r
    before = sys.pycache_prefix, sys.dont_write_bytecode
    pl, pltpu = jax_compat.import_pallas()
    assert pl.pallas_call and pltpu.make_async_copy
    assert (sys.pycache_prefix, sys.dont_write_bytecode) == before
    print(before)
"""


def _bytecode_files(folder):
    return sorted(os.path.join(d, f) for d, _, files in os.walk(folder)
                  for f in files if f.endswith(".pyc"))


def test_an_installation_that_keeps_no_bytecode_has_pallas_kept(tmp_path):
    """Where python writes no bytecode, every process compiles Pallas'
    modules from source (most of the import): theirs is kept beside the
    compiled programs, for the import's duration only, and a later
    process reads it."""
    out = _run(KEPT % str(tmp_path), PYTHONDONTWRITEBYTECODE="1",
               PYTHONPYCACHEPREFIX=None)
    assert out.strip().endswith("(None, True)")
    kept = _bytecode_files(tmp_path / "pycache")
    assert any("pallas" in f for f in kept)
    # only what this import ran: nothing of jax's own start
    assert not any(f.endswith(os.sep + "jax" + os.sep + "__init__.cpython-%d%d.pyc"
                              % sys.version_info[:2]) for f in kept)
    stamps = {f: os.stat(f).st_mtime_ns for f in kept}
    _run(KEPT % str(tmp_path), PYTHONDONTWRITEBYTECODE="1",
         PYTHONPYCACHEPREFIX=None)
    assert {f: os.stat(f).st_mtime_ns
            for f in _bytecode_files(tmp_path / "pycache")} == stamps


def test_an_installation_that_keeps_bytecode_is_left_alone(tmp_path):
    elsewhere = str(tmp_path / "theirs")
    out = _run(KEPT % str(tmp_path / "ours"), PYTHONDONTWRITEBYTECODE=None,
               PYTHONPYCACHEPREFIX=elsewhere)
    assert out.strip().endswith("(%r, False)" % elsewhere)
    assert _bytecode_files(tmp_path / "ours") == []


def test_nothing_is_imported_with_the_models():
    """Importing the learners imports no Pallas: the import happens when
    a step that uses the writer is traced."""
    out = _run("""
        import dmlc_tpu.models
        assert not [m for m in sys.modules if m.startswith(
            ("jax.experimental.pallas", "jax._src.pallas"))]
        print("ok")
    """)
    assert out.strip().endswith("ok")


def test_the_package_imports_pallas_from_nowhere_else():
    pattern = re.compile(
        r"^\s*(from\s+jax\.experimental(\.pallas\S*)?\s+import\s+(pallas|\S+)"
        r"|import\s+jax\.experimental\.pallas)", re.M)
    found = []
    for folder, _, files in os.walk(os.path.join(ROOT, "dmlc_tpu")):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(folder, name)
            with open(path) as f:
                text = f.read()
            for m in pattern.finditer(text):
                if "pallas" in m.group(0):
                    found.append((os.path.relpath(path, ROOT),
                                  m.group(0).strip()))
    # jax's own flash-attention kernel is a module of Pallas: it is
    # imported after import_pallas(), which the line before it calls
    allowed = [("dmlc_tpu/ops/sequence_parallel.py",
                "from jax.experimental.pallas.ops.tpu.flash_attention import (")]
    assert [f for f in found if f not in allowed] == []
    with open(os.path.join(ROOT, "dmlc_tpu/ops/pallas_kernels.py")) as f:
        kernels = f.read()
    assert "pl, pltpu = import_pallas()" in kernels
    with open(os.path.join(ROOT, "dmlc_tpu/ops/sequence_parallel.py")) as f:
        text = f.read()
    assert text.index("import_pallas()") < text.index(
        "from jax.experimental.pallas.ops.tpu.flash_attention")
