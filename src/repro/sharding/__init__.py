from repro.sharding.partitioning import (
    filter_spec, maybe_shard, shape_safe_shardings, tree_shardings,
)

__all__ = ["filter_spec", "maybe_shard", "shape_safe_shardings",
           "tree_shardings"]
