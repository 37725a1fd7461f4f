from repro.sharding.specs import (RULES, constrain, make_pspec, set_mesh,  # noqa: F401
                                  get_mesh, mesh_context, param_sharding)
from repro.sharding.specs import (DeviceRing, batch_devices,  # noqa: F401
                                  shard_mesh)
from repro.sharding.plan_shard import (ShardedRelationPlan,  # noqa: F401
                                       shard_relation_plan)
