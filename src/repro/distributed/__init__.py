"""Distribution: logical sharding rules and the data-plane shard router (one
logical stage over N stage processes)."""
from .router import AllShardsDownError, LocalShardHandle, ShardRouter
from .sharding import DEFAULT_RULES, active_mesh, logical_to_spec, lsc, named_sharding, sharding_rules

__all__ = [
    "AllShardsDownError",
    "DEFAULT_RULES",
    "LocalShardHandle",
    "ShardRouter",
    "active_mesh",
    "logical_to_spec",
    "lsc",
    "named_sharding",
    "sharding_rules",
]
