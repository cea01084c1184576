# Pallas TPU kernels for the paper's compute hot-spots:
#   block_spmm        — blocked-sparse aggregation (GHOST aggregate stage)
#   fused_block_spmm  — aggregation with the combine matmul (+bias/activation)
#                       fused into the SpMM epilogue, so the aggregated
#                       intermediate never round-trips through HBM
#   quant_matmul      — int8 sign-split MVM (GHOST combine stage)
#   attention_block_spmm — GAT's masked edge softmax over the blocked
#                       adjacency, online per destination row, self term in
# ops.py holds the jit'd wrappers (interpret=True on CPU); ref.py the oracles.
# autotune.py searches the fused/unfused config space per shape class and
# persists winners (serving resolves them at trace-build time).
from repro.kernels.ops import (
    aggregate_blocked_kernel,
    attention_block_spmm_padded,
    block_spmm_padded,
    fused_block_spmm_padded,
    quantized_matmul_kernel,
)
from repro.kernels.autotune import (
    Autotuner,
    AutotuneCache,
    KernelConfig,
    ShapeClass,
    candidate_configs,
    synthesize_problem,
)
