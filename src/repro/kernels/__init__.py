# Pallas TPU kernels for the paper's compute hot-spot: the fused
# quantize+chunked-accumulation GEMM (one pallas_call per GEMM), the
# standalone reference kernels it replaced, and the block-size autotuner.
from repro.kernels import autotune  # noqa: F401
from repro.kernels.attention import flash_prefill_paged, paged_attn_decode  # noqa: F401
from repro.kernels.autotune import get_kernel, register_kernel, registered_kernels  # noqa: F401
from repro.kernels.bwd_pair import qmatmul_bwd_pair, qmatmul_bwd_pair_nsplit  # noqa: F401
from repro.kernels.common import count_pallas_calls  # noqa: F401
from repro.kernels.fused import qmatmul_fused  # noqa: F401
from repro.kernels.ops import QDotConfig, qdot, qdot_packed, quantize_op  # noqa: F401
from repro.kernels.qmatmul import qmatmul_pallas  # noqa: F401
from repro.kernels.quantize import quantize_pallas  # noqa: F401
