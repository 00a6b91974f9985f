from repro.parallel.sharding import (
    AxisRules,
    DEFAULT_RULES,
    auto_mesh,
    axis_rules,
    current_rules,
    logical_to_spec,
    shard,
)

# the dispatch-backend registry (one MoE pipeline over pluggable
# fabrics; see docs/fabric.md).  Imported last: fabric modules import
# repro.parallel.sharding/collectives directly, never this package.
from repro.parallel import fabric

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "auto_mesh",
    "axis_rules",
    "current_rules",
    "fabric",
    "logical_to_spec",
    "shard",
]
