"""Hand-written TPU kernels for the hot ops.

XLA fusion already covers most of what the reference's JNI BLAS layer did
(SURVEY §2.6: Janino codegen and netlib dispatch both collapse into jit).
These Pallas kernels target the residual wins: keeping the whole
aggregate-block pipeline (margin → multiplier → transpose-matmul) resident
in VMEM across a row-tile grid, so HBM sees each instance block exactly once
per L-BFGS evaluation instead of once per op.
"""

from cycloneml_tpu.ops.kernels import (fused_binary_logistic,
                                       fused_binary_logistic_scaled,
                                       fused_kmeans_assign,
                                       fused_least_squares_scaled,
                                       fused_moment_gramian,
                                       fused_multinomial_logistic_scaled,
                                       moment_sums, pallas_available,
                                       use_fused_kernels)

__all__ = ["fused_binary_logistic", "fused_binary_logistic_scaled",
           "fused_kmeans_assign", "fused_least_squares_scaled",
           "fused_moment_gramian", "fused_multinomial_logistic_scaled",
           "moment_sums", "pallas_available", "use_fused_kernels"]
