"""Distributed runtime: sharding rules, train steps, fault tolerance, and
the typed EP-SpMV request layer (GraphServer + bucketed compilation)."""
from .fault import (
    CircuitBreaker,
    FaultTolerantLoop,
    HeartbeatRegistry,
    OverloadSchedule,
    StragglerMonitor,
)
from .request import (
    BucketKey,
    BucketPolicy,
    CompileCache,
    GraphRequest,
    GraphServer,
    ServeInfo,
    ServeResult,
    resolve_plan,
)
from .sharding import (
    ShardingRules,
    batch_specs,
    cache_spec_tree,
    make_sharding_rules,
    named,
    param_specs,
    tree_named,
)
from .train import TrainState, init_train_state, make_train_step, split_microbatches

__all__ = [
    "BucketKey",
    "BucketPolicy",
    "CompileCache",
    "CircuitBreaker",
    "FaultTolerantLoop",
    "GraphRequest",
    "GraphServer",
    "HeartbeatRegistry",
    "ServeInfo",
    "ServeResult",
    "ShardingRules",
    "OverloadSchedule",
    "StragglerMonitor",
    "TrainState",
    "batch_specs",
    "cache_spec_tree",
    "init_train_state",
    "make_sharding_rules",
    "make_train_step",
    "named",
    "param_specs",
    "resolve_plan",
    "split_microbatches",
    "tree_named",
]
