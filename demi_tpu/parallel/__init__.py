from ..obs import spans as _spans

with _spans.stage("setup.import", module=__name__):
    from .mesh import (
        make_mesh,
        pad_batch_to_devices,
        shard_explore_kernel,
        shard_replay_kernel,
        sweep_sharding,
    )

__all__ = [
    "make_mesh",
    "pad_batch_to_devices",
    "shard_explore_kernel",
    "shard_replay_kernel",
    "sweep_sharding",
]
