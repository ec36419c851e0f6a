"""TPU hot-path ops: Pallas kernels + XLA-fused primitives.

Equivalent capability: the reference's CUDA op zoo — flash-attention
wrappers (atorch/atorch/modules/transformer/layers.py:1168-1650), fused
cross-entropy (modules/transformer/cross_entropy.py), and the C++/CUDA
quantization kernels (atorch/atorch/ops/csrc/quantization/). TPU
redesign: Pallas/Mosaic kernels targeting the MXU/VPU, with interpret-mode
execution on CPU for tests.
"""

from dlrover_tpu.ops.attention import (  # noqa: F401
    flash_attention,
    flash_attention_bshd,
    mha_reference,
)
from dlrover_tpu.ops.cross_entropy import (  # noqa: F401
    softmax_cross_entropy,
    vocab_parallel_cross_entropy,
)
from dlrover_tpu.ops.quantization import (  # noqa: F401
    quantize_int8,
    dequantize_int8,
)
from dlrover_tpu.ops.collectives import (  # noqa: F401
    ring_all_gather,
    ring_reduce_scatter,
)
from dlrover_tpu.ops.fused_optim import fused_adamw  # noqa: F401
