from repro.distributed.amax_sync import (all_reduce_amax, host_amax_sync,
                                         make_amax_sync)
from repro.distributed.sharding import (batch_specs, param_specs,
                                        shard_map, state_specs,
                                        zero1_specs)
from repro.distributed.strategy import (DataParallel, ParallelPlan,
                                        TensorParallel, ZeRO1Sharded)

__all__ = ["batch_specs", "param_specs", "state_specs", "zero1_specs",
           "shard_map",
           "all_reduce_amax", "host_amax_sync", "make_amax_sync",
           "DataParallel", "ZeRO1Sharded", "TensorParallel", "ParallelPlan"]
