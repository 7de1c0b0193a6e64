#!/usr/bin/env python
"""Lint the in-tree partition-rule tables against their param trees.

The SPMD contract (parallel/partition.py) is that parameter placement is
DATA: a ``(regex, PartitionSpec)`` table matched against pytree leaf
names. Two table bugs are silent at authoring time and expensive at run
time:

- a non-scalar leaf NO rule matches — ``match_partition_rules`` raises,
  but only once a step is actually built on a mesh (tests on the
  single-device path never notice);
- a leaf matched by MORE than one rule — first-match order becomes
  load-bearing, and a later table edit reorders placement without any
  error anywhere.

This lint walks every registered rule table with a representative
parameter template and fails on either. Every ``*_PARTITION_RULES``
table exported from ``dmlc_tpu.models`` must be registered in ``CASES``
below — an unregistered table fails the lint too (the same
discoverability contract as scripts/check_faultpoints.py).

Run directly (exit 0/1) or via tests/test_partition.py.
"""

from __future__ import annotations

import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def build_cases():
    import jax

    from dmlc_tpu.models.ffm import (
        FFM_FACTOR_PARTITION_RULES,
        init_ffm_params,
    )
    from dmlc_tpu.models.fm import (
        FM_FACTOR_PARTITION_RULES,
        FM_PARTITION_RULES,
        init_fm_params,
    )
    from dmlc_tpu.models.linear import (
        LINEAR_MP_PARTITION_RULES,
        LINEAR_PARTITION_RULES,
        init_linear_params,
    )

    # abstract templates: leaf NAMES and shapes are what the lint needs,
    # never device buffers
    linear_t = jax.eval_shape(lambda: init_linear_params(8))
    fm_t = jax.eval_shape(lambda: init_fm_params(8, 4))
    # with the state tables of a stateful optimizer beside the weights
    fm_state_t = jax.eval_shape(
        lambda: init_fm_params(8, 4, optimizer="ftrl_adagrad"))
    # the field-aware FM: v and its accumulator, 2-D, nothing else
    ffm_t = jax.eval_shape(lambda: init_ffm_params(8, 2, 3))
    return (
        ("FFM_FACTOR_PARTITION_RULES", FFM_FACTOR_PARTITION_RULES, ffm_t),
        ("LINEAR_PARTITION_RULES", LINEAR_PARTITION_RULES, linear_t),
        ("LINEAR_MP_PARTITION_RULES", LINEAR_MP_PARTITION_RULES, linear_t),
        ("FM_PARTITION_RULES", FM_PARTITION_RULES, fm_t),
        ("FM_FACTOR_PARTITION_RULES", FM_FACTOR_PARTITION_RULES, fm_t),
        ("FM_PARTITION_RULES", FM_PARTITION_RULES, fm_state_t),
        ("FM_FACTOR_PARTITION_RULES", FM_FACTOR_PARTITION_RULES, fm_state_t),
    )


def exported_tables() -> set:
    """Names of every *_PARTITION_RULES constant defined under
    dmlc_tpu/models — the set CASES must cover."""
    import re

    names = set()
    table_re = re.compile(r"^([A-Z0-9_]+_PARTITION_RULES)\s*=", re.M)
    for path in sorted((ROOT / "dmlc_tpu" / "models").glob("*.py")):
        names.update(table_re.findall(path.read_text()))
    return names


def run() -> int:
    from dmlc_tpu.parallel.partition import lint_partition_rules

    cases = build_cases()
    problems = []
    covered = {name for name, _, _ in cases}
    for missing in sorted(exported_tables() - covered):
        problems.append(
            f"{missing}: defined in dmlc_tpu/models but not registered in "
            "scripts/check_partition_rules.py CASES (unlinted table)"
        )
    for name, rules, template in cases:
        for issue in lint_partition_rules(rules, template):
            problems.append(f"{name}: {issue}")
    if problems:
        for p in problems:
            print(f"check_partition_rules: {p}", file=sys.stderr)
        return 1
    print(
        f"check_partition_rules: OK ({len(cases)} tables, every non-scalar "
        "leaf matches exactly one rule)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(run())
