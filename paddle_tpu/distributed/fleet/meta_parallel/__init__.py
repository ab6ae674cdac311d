from .mp_layers import (  # noqa: F401
    ColumnParallelLinear, RowParallelLinear, VocabParallelEmbedding,
    ParallelCrossEntropy,
)
from .pp_layers import (  # noqa: F401
    LayerDesc, SharedLayerDesc, PipelineLayer, PipelineParallel,
)
from .random_ctrl import (  # noqa: F401
    RNGStatesTracker, get_rng_state_tracker, model_parallel_random_seed,
)
from .sharding import (  # noqa: F401
    group_sharded_parallel, shard_optimizer_states,
)
from .pipeline_schedule import (  # noqa: F401
    spmd_pipeline, spmd_pipeline_1f1b, pipeline_tick_stats)
from .moe import DroplessMoELayer, MoELayer, top2_gating  # noqa: F401
from .sep_utils import (  # noqa: F401
    sep_attention, alltoall_seq_to_heads, alltoall_heads_to_seq,
)
