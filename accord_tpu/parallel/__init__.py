"""Multi-chip parallelism: mesh construction + sharded protocol kernels."""

from .sharded import (STORE_AXIS, make_mesh, shard_bucket_table, shard_table,
                      sharded_calculate_deps, sharded_drain,
                      sharded_protocol_step)

__all__ = ["STORE_AXIS", "make_mesh", "shard_bucket_table", "shard_table",
           "sharded_calculate_deps", "sharded_drain",
           "sharded_protocol_step"]
