from .compression import (EFState, compressed_worker_mean, dequantize_int8,
                          ef_init, quantize_int8)
from .sharding import (batch_shardings, cache_shardings, leaf_spec, named,
                       param_shardings, place_worker_axis, worker_mesh)

__all__ = [
    "EFState", "compressed_worker_mean", "dequantize_int8", "ef_init",
    "quantize_int8", "batch_shardings", "cache_shardings", "leaf_spec",
    "named", "param_shardings", "place_worker_axis", "worker_mesh",
]
