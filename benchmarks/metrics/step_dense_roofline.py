"""step_dense_roofline: see step_dense_roofline.json beside this file."""

import os

from harness import spec

_dense_ms = spec.load_module(
    os.path.join(os.path.dirname(__file__), "step_dense_ms.py")).read


def read(run):
    needs = getattr(run["config"], "dense_needs", None)
    ms = _dense_ms(run)
    if needs is None or not ms or not run["peaks"]:
        return None
    flops = needs(run["cfg"], run["batch_rows"] // run["chips"])["flops"]
    return 100.0 * flops / run["peaks"]["flops_per_s"] / (ms / 1e3)
