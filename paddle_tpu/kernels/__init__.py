"""TPU kernels: Pallas implementations of the hot fused ops.

The native-kernel tier of the framework — the analogue of the reference's
hand-written CUDA fused ops (/root/reference/paddle/fluid/operators/fused/)
and math library (operators/math/), rebuilt as Pallas/Mosaic kernels.
The dispatch (attention.py, ops/nn_ops.py) picks a kernel or its XLA
composition from the platform and the shape; a kernel that was picked and
fails to compile raises, it does not fall back.
"""

from . import attention  # noqa: F401
