"""Partition-rule tables: regex → PartitionSpec matching, the
exactly-one-rule lint, and rule-driven mesh placement
(dmlc_tpu/parallel/partition.py + scripts/check_partition_rules.py)."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dmlc_tpu.parallel.partition import (
    REPLICATED_RULES,
    leaf_names,
    lint_partition_rules,
    match_partition_rules,
    named_tree_map,
    shard_params,
    sharding_tree,
)
from dmlc_tpu.utils.logging import DMLCError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tree():
    return {
        "layers": [
            {"kernel": jnp.ones((4, 8)), "bias": jnp.zeros((8,))},
            {"kernel": jnp.ones((8, 2)), "bias": jnp.zeros((2,))},
        ],
        "head": {"w": jnp.ones((2, 3))},
        "step": jnp.zeros(()),  # scalar: never consults the table
    }


class TestNaming:
    def test_leaf_names_are_slash_joined_paths(self):
        names = leaf_names(_tree())
        assert "layers/0/kernel" in names
        assert "layers/1/bias" in names
        assert "head/w" in names
        assert "step" in names

    def test_named_tree_map_passes_names(self):
        seen = {}
        named_tree_map(lambda n, leaf: seen.setdefault(n, leaf.shape),
                       _tree())
        assert seen["layers/0/kernel"] == (4, 8)
        assert seen["head/w"] == (2, 3)


class TestMatch:
    RULES = (
        (r"head/w", P("mp")),
        (r"kernel", P(None, "mp")),
        (r"bias", P()),
    )

    def test_first_match_wins_and_scalars_replicate(self):
        specs = match_partition_rules(self.RULES, _tree())
        assert specs["layers"][0]["kernel"] == P(None, "mp")
        assert specs["layers"][1]["bias"] == P()
        assert specs["head"]["w"] == P("mp")
        # rank-0 leaf replicated without any rule consulted
        assert specs["step"] == P()

    def test_scalar_matches_no_rule_yet_never_raises(self):
        # a table that matches nothing still handles a scalar-only tree
        specs = match_partition_rules(((r"^zzz$", P("mp")),),
                                      {"step": jnp.zeros(())})
        assert specs["step"] == P()

    def test_unmatched_leaf_raises(self):
        with pytest.raises(DMLCError, match="no partition rule matches"):
            match_partition_rules(((r"^kernel$", P()),), _tree())

    def test_replicated_rules_cover_everything(self):
        specs = match_partition_rules(REPLICATED_RULES, _tree())
        assert all(s == P() for s in jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P)))


class TestLint:
    def test_clean_table_returns_no_problems(self):
        assert lint_partition_rules(TestMatch.RULES, _tree()) == []

    def test_reports_unmatched_leaf(self):
        problems = lint_partition_rules(((r"kernel", P()),), _tree())
        assert any("head/w: matched by no rule" in p for p in problems)
        # scalars stay exempt even under a table that misses them
        assert not any(p.startswith("step") for p in problems)

    def test_reports_ambiguous_match(self):
        rules = ((r"head/w", P("mp")), (r".*", P()))
        problems = lint_partition_rules(rules, _tree())
        assert any("head/w: matched by 2 rules" in p for p in problems)


class TestShardParams:
    def test_places_leaves_with_rule_shardings(self):
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        params = {"w": jnp.arange(16, dtype=jnp.float32),
                  "b": jnp.zeros(())}
        placed = shard_params(params, mesh,
                              rules=((r"^w$", P("dp")), (r"^b$", P())))
        assert placed["w"].sharding == NamedSharding(mesh, P("dp"))
        assert placed["b"].sharding == NamedSharding(mesh, P())
        np.testing.assert_array_equal(np.asarray(placed["w"]),
                                      np.arange(16, dtype=np.float32))

    def test_default_rules_replicate(self):
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        placed = shard_params({"w": jnp.ones((8,))}, mesh)
        assert placed["w"].sharding == NamedSharding(mesh, P())

    def test_precomputed_specs_beat_rules(self):
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        placed = shard_params(
            {"w": jnp.ones((8,))}, mesh,
            rules=((r".*", P()),), specs={"w": P("dp")})
        assert placed["w"].sharding == NamedSharding(mesh, P("dp"))

    def test_fm_factor_rules_divide_the_tables_columns(self):
        """FM_FACTOR_PARTITION_RULES: a chip holds a column slice of v
        and whole replicas of w and b."""
        from dmlc_tpu.models.fm import (
            FM_FACTOR_PARTITION_RULES,
            FM_PARTITION_RULES,
            fm_partition_rules,
            init_fm_params,
        )

        assert fm_partition_rules("factors") is FM_FACTOR_PARTITION_RULES
        assert fm_partition_rules("replicated") is FM_PARTITION_RULES
        template = jax.eval_shape(lambda: init_fm_params(16, 8))
        assert match_partition_rules(
            FM_FACTOR_PARTITION_RULES, template) == {
                "w": P(), "b": P(), "v": P(None, "dp")}
        assert lint_partition_rules(
            FM_FACTOR_PARTITION_RULES, template) == []
        with pytest.raises(DMLCError, match="table_sharding"):
            fm_partition_rules("rows")

        mesh = Mesh(np.asarray(jax.devices()[:4]), ("dp",))
        placed = shard_params(
            init_fm_params(16, 8), mesh, rules=FM_FACTOR_PARTITION_RULES)
        assert placed["v"].sharding == NamedSharding(mesh, P(None, "dp"))
        assert {s.data.shape for s in placed["v"].addressable_shards} == {
            (16, 2)}
        assert placed["w"].sharding == NamedSharding(mesh, P())
        assert placed["w"].addressable_shards[0].data.shape == (16,)

    def test_sharding_tree_maps_specs(self):
        mesh = Mesh(np.asarray(jax.devices()), ("dp",))
        tree = sharding_tree(mesh, {"a": P("dp"), "b": P()})
        assert tree["a"] == NamedSharding(mesh, P("dp"))
        assert tree["b"] == NamedSharding(mesh, P())


class TestCheckScript:
    """scripts/check_partition_rules.py is the CI gate for the in-tree
    tables; it must pass on the shipped tables and notice an
    unregistered one."""

    def _mod(self):
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "check_partition_rules",
            os.path.join(REPO, "scripts", "check_partition_rules.py"),
        )
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def test_in_tree_tables_are_clean(self):
        assert self._mod().run() == 0

    def test_cases_cover_every_exported_table(self):
        mod = self._mod()
        assert {n for n, _, _ in mod.build_cases()} == mod.exported_tables()

    def test_script_exits_zero(self):
        proc = subprocess.run(
            [sys.executable,
             os.path.join(REPO, "scripts", "check_partition_rules.py")],
            capture_output=True, text=True, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 0, proc.stderr
        assert "OK" in proc.stdout
