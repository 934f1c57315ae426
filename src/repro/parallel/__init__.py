"""Sharding utilities: logical-axis rules mapping model annotations onto
mesh axes."""
from .sharding import axis_rules, shard, logical_to_spec, named_sharding, current_mesh

__all__ = ["axis_rules", "shard", "logical_to_spec", "named_sharding", "current_mesh"]
