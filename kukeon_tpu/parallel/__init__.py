from kukeon_tpu.parallel.mesh import (  # noqa: F401
    AXIS_DATA,
    AXIS_EXPERT,
    AXIS_PIPE,
    AXIS_FSDP,
    AXIS_SEQ,
    AXIS_TENSOR,
    auto_mesh_shape,
    make_mesh,
    serving_mesh,
    training_mesh,
)
from kukeon_tpu.parallel.pipeline import (  # noqa: F401
    make_pp_train_step,
    pipeline_forward,
    pp_param_specs,
    pp_specs_for_params,
)
from kukeon_tpu.parallel.ring_attention import ring_attention  # noqa: F401
from kukeon_tpu.parallel.ulysses import ulysses_attention  # noqa: F401
from kukeon_tpu.parallel.sharding import (  # noqa: F401
    batch_spec,
    kv_cache_spec,
    llama_param_specs,
    moe_param_specs,
    moe_specs_for_params,
    shard_params,
    specs_for_params,
)
