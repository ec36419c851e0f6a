"""TPU-native parallelism fabric.

Equivalent capability: reference atorch/atorch/distributed/distributed.py
(create_parallel_group :321, parallel_group/parallel_rank :83-117) and the
atorch auto_accelerate strategy machinery (atorch/atorch/auto/) — but
re-designed for the XLA/GSPMD compilation model: instead of building nested
torch process groups and wrapping modules, we build one
``jax.sharding.Mesh`` with named axes and express every parallelism as a
sharding rule over those axes. XLA inserts the collectives.
"""

from dlrover_tpu.parallel.mesh import (  # noqa: F401
    MeshConfig,
    build_mesh,
    get_mesh,
    set_mesh,
    axis_size,
    axis_index,
)
from dlrover_tpu.parallel.sharding import (  # noqa: F401
    LogicalRules,
    DEFAULT_RULES,
    logical_sharding,
    shard_logical,
    unsharded,
)
from dlrover_tpu.parallel.strategy import (  # noqa: F401
    Strategy,
    auto_strategy,
    load_strategy,
    save_strategy,
)
from dlrover_tpu.parallel.accelerate import (  # noqa: F401
    AccelerateResult,
    auto_accelerate,
)
from dlrover_tpu.parallel.adapter import (  # noqa: F401
    StackedModule,
    accelerate_module,
    infer_logical_axes,
    stack_layer_params,
)
from dlrover_tpu.parallel.pipeline import (  # noqa: F401
    pipe_size,
    pipeline_apply,
    pipeline_loss_1f1b,
    stage_layer_scan,
)
from dlrover_tpu.parallel.moe import (  # noqa: F401
    MoEConfig,
    moe_ffn,
    moe_init,
    top_k_gating,
)
from dlrover_tpu.parallel.sequence import (  # noqa: F401
    ring_attention,
    sequence_sharded_attention,
    ulysses_attention,
)
from dlrover_tpu.parallel.engine import (  # noqa: F401
    DryRunner,
    DryRunResult,
    ModelAnalysis,
    StrategySearchEngine,
    analyse_params,
    candidate_strategies,
    estimate_hbm_per_device,
    search_strategy,
)
