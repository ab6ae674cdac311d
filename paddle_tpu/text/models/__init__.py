from .bert import (  # noqa: F401
    BertModel, BertForSequenceClassification, BertForPretraining,
    BertPretrainingCriterion, ErnieModel, ErnieForSequenceClassification,
)
from .gpt import (  # noqa: F401
    GPTModel, GPTForCausalLM, GPTForCausalLMPipe, GPTDecoderLayer,
    stack_block_params, block_fn_for, pipeline_forward,
)
from .llama import (  # noqa: F401
    LlamaConfig, LlamaForCausalLM, LlamaModel,
)
from .deepseek_v3 import (  # noqa: F401
    DeepseekV3Config, DeepseekV3ForCausalLM, DeepseekV3Model,
)
from .lfm2 import (  # noqa: F401
    Lfm2MoeConfig, Lfm2MoeForCausalLM, Lfm2MoeModel,
)
from .ouro import (  # noqa: F401
    OuroConfig, OuroForCausalLM, OuroModel,
)
from .smallthinker import (  # noqa: F401
    SmallThinkerConfig, SmallThinkerForCausalLM, SmallThinkerModel,
)
