from repro.distributed.compression import (  # noqa: F401
    ef_compressed_mean,
    init_error_state,
    tree_ef_compressed_mean,
    wire_bytes_fp32_allreduce,
    wire_bytes_int8_gather,
)
from repro.distributed.fault import StepWatchdog, run_with_restarts  # noqa: F401
from repro.distributed.sharding import (  # noqa: F401
    abstract_with_sharding,
    batch_specs,
    named_shardings,
    param_specs,
)
