"""Model zoo: TPU-first reference models used by the trainer, the benchmark
and the auto_accelerate strategy tests.

Equivalent capability: the reference accelerates HF models (Llama/GPT2/
GLM/Bert attention swaps, atorch/atorch/modules/transformer/layers.py) and
ships Llama-2 examples (atorch/examples/llama2). TPU redesign: a native
functional decoder (scan-over-layers, logical sharding axes, flash
attention) rather than module injection into torch models.
"""

from dlrover_tpu.models.llama import (  # noqa: F401
    LlamaConfig,
    llama_logical_axes,
    llama_init,
    llama_apply,
    llama_loss_fn,
    PRESETS,
)

from dlrover_tpu.models.recsys import (  # noqa: F401
    RecsysConfig,
    TieredBatchPreparer,
    make_tiered_embedding,
    recsys_init,
    recsys_logical_axes,
    recsys_loss_fn,
)

from dlrover_tpu.models.gpt2 import (  # noqa: F401
    GPT2Config,
    GPT2_PRESETS,
    gpt2_logical_axes,
    gpt2_init,
    gpt2_apply,
    gpt2_loss_fn,
)

from dlrover_tpu.models.granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    granite_hybrid_logical_axes,
    granite_hybrid_init,
    granite_hybrid_apply,
    granite_hybrid_loss_fn,
)

from dlrover_tpu.models.olmo_hybrid import (  # noqa: F401
    OlmoHybridConfig,
    olmo_hybrid_logical_axes,
    olmo_hybrid_init,
    olmo_hybrid_apply,
    olmo_hybrid_loss_fn,
)
