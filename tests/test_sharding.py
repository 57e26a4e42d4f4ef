"""Logical-axis rule engine: divisibility guards, axis-reuse guards."""
import numpy as np
import pytest

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.sharding.logical import (A, DEFAULT_RULES, SP_DECODE_RULES,
                                    ShardingRules, param_specs, spec_for)


def _mesh(shape=(2, 2), axes=("data", "model")):
    # a fake mesh over the single CPU device repeated is not allowed;
    # use an abstract mesh for spec resolution (spec_for only needs names
    # and sizes, not devices).
    return jax.sharding.AbstractMesh(shape, axes)


class TestSpecFor:
    def test_basic_tp(self):
        m = _mesh((4, 2))
        sp = spec_for(m, (64, 128), ("embed", "mlp"))
        assert sp == P("data", "model")

    def test_divisibility_guard(self):
        m = _mesh((4, 2))
        # 6 % 4 != 0 -> embed falls to replicated; mlp still shards
        sp = spec_for(m, (6, 128), ("embed", "mlp"))
        assert sp == P(None, "model")

    def test_axis_used_once(self):
        m = _mesh((2, 2))
        # both dims want 'model': second falls back to replicated
        sp = spec_for(m, (32, 32), ("heads", "mlp"))
        assert sp == P("model")

    def test_multi_axis_candidate(self):
        m = _mesh((2, 4, 2), ("pod", "data", "model"))
        sp = spec_for(m, (16, 128), ("batch", "act_seq"))
        assert sp == P(("pod", "data"))

    def test_multi_axis_divisibility(self):
        m = _mesh((2, 4, 2), ("pod", "data", "model"))
        # batch 6 not divisible by pod*data=8 nor data=4 -> replicated
        sp = spec_for(m, (6, 128), ("batch", "act_seq"))
        assert sp == P()

    def test_unknown_name_replicates(self):
        m = _mesh()
        assert spec_for(m, (8,), ("nonexistent",)) == P()

    def test_kv_seq_rules(self):
        m = _mesh((2, 4, 2), ("pod", "data", "model"))
        # default: kv_seq -> model
        sp = spec_for(m, (2, 64, 8, 16),
                      ("batch", "kv_seq", "kv_heads", None))
        assert sp[1] == "model"
        # SP decode: kv_seq -> (data, model)
        sp = spec_for(m, (1, 64, 8, 16),
                      ("batch", "kv_seq", "kv_heads", None),
                      SP_DECODE_RULES)
        assert sp[1] == ("data", "model")

    def test_gqa_kv_heads_guard(self):
        m = _mesh((1, 16), ("data", "model"))
        # kv_heads=8 cannot shard over model=16 -> replicated
        sp = spec_for(m, (128, 8, 64), ("embed", "kv_heads", "head"))
        assert sp == P()


class TestParamSpecs:
    def test_structure_and_annotation(self):
        m = _mesh((2, 2))
        shapes = {"w": jax.ShapeDtypeStruct((64, 32), np.float32),
                  "nested": {"b": jax.ShapeDtypeStruct((32,), np.float32)}}
        axes = {"w": A("embed", "mlp"), "nested": {"b": A(None)}}
        specs = param_specs(shapes, axes, m)
        assert specs["w"] == P("data", "model")
        assert specs["nested"]["b"] == P()

    def test_A_is_leaf(self):
        ax = {"x": A("embed", "mlp")}
        leaves = jax.tree_util.tree_leaves(ax)
        assert len(leaves) == 1 and isinstance(leaves[0], A)

    def test_overrides(self):
        rules = DEFAULT_RULES.with_overrides(act_seq=["model"])
        m = _mesh((2, 2))
        sp = spec_for(m, (4, 64, 32), ("batch", "act_seq", "act_embed"),
                      rules)
        assert sp == P("data", "model")
        # base rules unchanged (immutability)
        sp2 = spec_for(m, (4, 64, 32), ("batch", "act_seq", "act_embed"))
        assert sp2 == P("data")
