"""FedZO reproduction package.

Every ZO loss query is a finite difference: the estimator divides
L(x + μv) − L(x) by μ, with perturbation entries around μ/√d. On a TPU an
fp32 matmul or convolution by default makes one bfloat16 pass, whose 8-bit
mantissa rounds such perturbations of the weights away, and the wide
path's update matvec and the masked aggregation einsums lose precision the
same way. So the package asks for full fp32 matmul precision before any
program is traced. On the CPU this changes nothing: fp32 dots there are
fp32 already.

Seed replay is sharding-invariant because jax's threefry is partitionable
(the default since jax 0.5): a direction generated inside a
GSPMD-partitioned program is bit-equal to the one a receiver regenerates
elsewhere from the same key.
"""
import jax as _jax

_jax.config.update("jax_default_matmul_precision", "highest")
