from repro.comms.executor import (
    BufferPlan,
    clear_plan_cache,
    execute_program,
    plan_buffers,
    plan_buffers_cached,
)
from repro.comms.primitives import (
    CollectiveSpec,
    lower_algorithm,
    pccl_all_gather,
    pccl_all_reduce,
    pccl_all_to_all,
    pccl_reduce_scatter,
    synthesize_program,
)
from repro.comms.compression import (
    ef_int8_compress,
    ef_int8_decompress,
    error_feedback_all_reduce,
    topk_compress,
    topk_decompress,
)

__all__ = [
    "BufferPlan",
    "clear_plan_cache",
    "execute_program",
    "plan_buffers",
    "plan_buffers_cached",
    "CollectiveSpec",
    "lower_algorithm",
    "pccl_all_gather",
    "pccl_all_reduce",
    "pccl_all_to_all",
    "pccl_reduce_scatter",
    "synthesize_program",
    "ef_int8_compress",
    "ef_int8_decompress",
    "error_feedback_all_reduce",
    "topk_compress",
    "topk_decompress",
]
