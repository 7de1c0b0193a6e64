"""The compiled step of every sparse-table learner the benchmark's older
cells run (FM dense and with the difacto rule, field-aware FM, the
memory-adaptive FM, the FM on a mesh), held to a digest of its lowered
text at small sizes on the CPU.

What it is for: a PR that touches ``models/fm.py`` or ``models/ffm.py``
and means to leave these steps alone (PR 42 split two helpers there for a
new learner) proves it here: no digest moves. A PR that means to change a
step, or an upgrade of jax, moves them, and that is no fault: run

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=. python tests/test_fm_step_programs.py

which prints ``PINNED_JAX`` and ``PROGRAMS`` as they are now (and, given a
directory, writes each step's text there, so that two checkouts can be
compared with ``diff``), and paste them below. Under another jax than the
pinned one the cases are skipped, not failed: the texts are jax's.
"""

import hashlib
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from dmlc_tpu.models import AdaptiveFMLearner, FFMLearner, FMLearner

#: the jax whose lowering the digests below are of
PINNED_JAX = "0.9.0"

#: sha256 of the lowered text of each step, by the cell that runs it; as
#: they were at PR 41 (f7cb7a0) and are after PR 42
PROGRAMS = {
    "kdd12-ffm":
        "1879677400c087ddcea5790c15aa724b46ba50dd7b03ef1b09d4ceffd1e3ba9a",
    "kdd12-fm":
        "04ebca4aa11353d059385b4770d68f4de345c8ae48062c58ac821c951c0c0437",
    "kdd12-fm-difacto":
        "7f9b753e1ace5775f1af7027597c141a18c0ec8f27cd8b3f01754b8280d1b194",
    "kdd12-fm-k128":
        "863ec053494fd28dc8e864a1133abfd6737830dad1eca2c6014adeffceef5d4f",
    "kdd12-fm-k128-adaptive":
        "16dc8eae5f7139488f7dceb9ed8b8701c29354cd4d4d1170eacd0f22d53bd913",
}


def _learner(name):
    rule = dict(optimizer="ftrl_adagrad", l1=1e-4, lr_beta=1e-3,
                v_learning_rate=0.01, v_lr_beta=1e-3, v_l2=1e-5)
    if name == "kdd12-fm":
        return FMLearner(num_features=1003, num_factors=16)
    if name == "kdd12-fm-difacto":
        return FMLearner(num_features=1003, num_factors=16, **rule)
    if name == "kdd12-ffm":
        return FFMLearner(
            num_features=1003, num_factors=2, field_sizes=(2, 100, 900))
    if name == "kdd12-fm-k128-adaptive":
        return AdaptiveFMLearner(
            num_features=1003, num_factors=16, factor_capacity=64,
            count_rows=4096, **rule)
    return FMLearner(
        mesh=Mesh(np.asarray(jax.devices()[:2]), ("dp",)),
        num_features=1003, num_factors=16, table_sharding="factors")


def _step_text(name) -> str:
    """The lowered text of the learner's own step over a batch of 8 rows."""
    batch = {
        "label": jnp.zeros((8,)), "weight": jnp.ones((8,)),
        "indices": jnp.zeros((24,), jnp.int32), "values": jnp.ones((24,)),
        "offsets": jnp.arange(9, dtype=jnp.int32) * 3}
    model = _learner(name)
    model.init_tables(0)
    model._ensure(1003)
    step = model._step
    while hasattr(step, "__wrapped__"):
        step = step.__wrapped__
    if model.mesh is not None:
        batch["offsets"] = jnp.tile(jnp.arange(5, dtype=jnp.int32) * 3, 2)
    return step.lower(model.params, batch).as_text()


def _program_digests(text_dir=None) -> dict:
    """{cell: sha256 of its step's text} as this checkout lowers them;
    with ``text_dir`` each text is written there as ``<cell>.txt``."""
    out = {}
    for name in sorted(PROGRAMS):
        text = _step_text(name)
        out[name] = hashlib.sha256(text.encode()).hexdigest()
        if text_dir:
            os.makedirs(text_dir, exist_ok=True)
            with open(os.path.join(text_dir, name + ".txt"), "w") as f:
                f.write(text)
    return out


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_step_lowers_to_the_pinned_text(name):
    if jax.__version__ != PINNED_JAX:
        pytest.skip("the digests are of jax %s's lowering, this is %s: "
                    "re-pin them (this file's docstring)"
                    % (PINNED_JAX, jax.__version__))
    digest = hashlib.sha256(_step_text(name).encode()).hexdigest()
    assert digest == PROGRAMS[name], (
        "the step of %s lowers to another program than the pinned one. If "
        "the change to models/fm.py or models/ffm.py means to change it, "
        "re-pin: `PYTHONPATH=. python tests/test_fm_step_programs.py` "
        "prints the digests as they are now; given a directory it writes "
        "the texts, to diff against the same from the parent's checkout"
        % name)


if __name__ == "__main__":
    digests = _program_digests(sys.argv[1] if len(sys.argv) > 1 else None)
    print('PINNED_JAX = "%s"' % jax.__version__)
    print("PROGRAMS = {")
    for cell, digest in digests.items():
        print('    "%s":\n        "%s",' % (cell, digest))
    print("}")
