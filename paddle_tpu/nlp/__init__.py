"""paddle_tpu.nlp — flagship language-model family.

The reference keeps its LLM zoo in PaddleNLP (SURVEY.md §6: the Llama-2-7B
Fleet hybrid-parallel config is the north-star benchmark); this module
provides the TPU-native equivalent built on the framework's own surface
(nn.Layer, fleet TP layers, Pallas flash attention, fused rope).
"""
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaAttention,
    LlamaMLP,
    LlamaDecoderLayer,
    LlamaModel,
    LlamaForCausalLM,
    LlamaPretrainingCriterion,
)
from .deepseek_v3 import (  # noqa: F401
    DeepseekV3Config, DeepseekV3Attention, DeepseekV3MLP, DeepseekV3MoE,
    DeepseekV3DecoderLayer, DeepseekV3Model, DeepseekV3ForCausalLM,
)
from .granitemoehybrid import (  # noqa: F401
    GraniteMoeHybridConfig, GraniteMoeHybridMamba, GraniteMoeHybridAttention,
    GraniteMoeHybridMoE, GraniteMoeHybridDecoderLayer, GraniteMoeHybridModel,
    GraniteMoeHybridForCausalLM,
)
from .afmoe import (  # noqa: F401
    AfmoeConfig, AfmoeAttention, AfmoeMoE, AfmoeDecoderLayer, AfmoeModel,
    AfmoeForCausalLM,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig, NemotronHMoE, NemotronHBlock, NemotronHModel,
    NemotronHForCausalLM,
)
from .falcon_h1 import (  # noqa: F401
    FalconH1Config, FalconH1Attention, FalconH1MLP, FalconH1DecoderLayer,
    FalconH1Model, FalconH1ForCausalLM,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config, KimiDeltaAttention, SolarOpen2Attention,
    SolarOpen2MoE, SolarOpen2DecoderLayer, SolarOpen2Model,
    SolarOpen2ForCausalLM,
)
from .gpt import GPTConfig, GPTModel, GPTForCausalLM  # noqa: F401
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForPretraining,
    BertForSequenceClassification, BertPretrainingCriterion,
    ErnieConfig, ErnieModel, ErnieForPretraining,
)
from .t5 import T5Config, T5Model, T5ForConditionalGeneration  # noqa: F401
from .paged_cache import PagedKVCachePool  # noqa: F401
