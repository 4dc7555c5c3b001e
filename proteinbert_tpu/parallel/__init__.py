from proteinbert_tpu.parallel.mesh import make_mesh, mesh_for_devices
from proteinbert_tpu.parallel.sharding import (
    batch_sharding, pin_state_sharding, serve_batch_sharding,
    state_sharding, shard_train_state,
)
from proteinbert_tpu.parallel.halo import (
    halo_exchange, conv1d_halo, seq_parallel_conv1d,
)
from proteinbert_tpu.parallel.multihost import maybe_initialize_distributed
from proteinbert_tpu.parallel.reshard import (
    mesh_from_config, parse_mesh_spec, reshard_checkpoint, reshard_state,
    reshard_schedule_bytes, states_byte_identical,
)
from proteinbert_tpu.parallel.seq_parallel import (
    make_seq_parallel_train_step, seq_parallel_apply, sharded_global_attention,
)
from proteinbert_tpu.parallel.zero import (
    make_zero_train_step, zero_extent, zero_gradient_update,
)

__all__ = [
    "make_mesh", "mesh_for_devices",
    "batch_sharding", "pin_state_sharding", "serve_batch_sharding",
    "state_sharding", "shard_train_state",
    "halo_exchange", "conv1d_halo", "seq_parallel_conv1d",
    "make_seq_parallel_train_step", "seq_parallel_apply",
    "sharded_global_attention", "maybe_initialize_distributed",
    "make_zero_train_step", "zero_extent", "zero_gradient_update",
    "mesh_from_config", "parse_mesh_spec", "reshard_checkpoint",
    "reshard_state", "reshard_schedule_bytes", "states_byte_identical",
]
