"""Logical-axis sharding system (MaxText-style rules -> PartitionSpec)."""
from repro.sharding.logical import (A, ShardingCtx, ShardingRules,
                                    DEFAULT_RULES, SP_DECODE_RULES,
                                    INPUT_PARALLEL_RULES, spec_for, shard,
                                    param_specs, param_shardings)
