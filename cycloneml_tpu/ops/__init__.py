"""Hand-written TPU kernels for the hot ops.

XLA fusion already covers most of what the reference's JNI BLAS layer did
(SURVEY §2.6: Janino codegen and netlib dispatch both collapse into jit).
These Pallas kernels target the residual wins: keeping the whole
aggregate-block pipeline (margin → multiplier → transpose-matmul) resident
in VMEM across a row-tile grid, so HBM sees each instance block exactly once
per L-BFGS evaluation instead of once per op — the GLM sweeps, the K-class
and stacked sweeps, the moment Gramian (``kernels``) and the Lloyd step
(``kmeans_lloyd``). Kernels take arrays and shapes;
``ml/optim/aggregators.dense_sweep`` chooses which sweep a fit runs.
"""

from cycloneml_tpu.ops.kernels import (fused_binary_logistic_scaled,
                                       fused_least_squares_scaled,
                                       fused_moment_gramian,
                                       fused_multinomial_logistic_scaled,
                                       moment_sums, pallas_available)

__all__ = ["fused_binary_logistic_scaled", "fused_least_squares_scaled",
           "fused_moment_gramian", "fused_multinomial_logistic_scaled",
           "moment_sums", "pallas_available"]
