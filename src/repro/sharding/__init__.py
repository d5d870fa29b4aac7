from repro.sharding.specs import (  # noqa: F401
    ShardingRules,
    batch_spec,
    build_param_specs,
    named_shardings,
)
