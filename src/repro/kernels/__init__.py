# Pallas TPU kernels for the compute hot-spots (validated in interpret
# mode on CPU, which ``backend.interpret_mode`` picks only there; see
# each package's kernel.py for the VMEM tiling):
#   approx_matmul    — the paper's technique: LUT behavioral oracle +
#                      rank-k MXU deployment
#   flash_attention  — fused blockwise attention (removes the dominant
#                      training-traffic class, §Perf)
#   selective_scan   — fused Mamba-1 scan (removes the SSM state-stream
#                      traffic, §Perf cell B)
#   population_lut   — the batched behavioral sim's population LUT
#                      gather (the fused engine inlines its XLA form;
#                      the Pallas form is refused by the TPU compiler)
from . import approx_matmul, flash_attention, population_lut, selective_scan

__all__ = [
    "approx_matmul", "flash_attention", "population_lut", "selective_scan",
]
