"""Flagship model families (reference: the fleet hybrid-parallel rank
scripts ``unittests/hybrid_parallel_mp_model.py`` / ``hybrid_parallel_pp_transformer.py``
and the ERNIE/GPT configs those tests model)."""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTModel,
    GPTForCausalLM,
    GPTDecoderLayer,
    GPTEmbeddings,
    build_gpt_pipeline_descs,
)
from .nemotron_h import (  # noqa: F401
    NemotronHConfig,
    NemotronHModel,
    NemotronHForCausalLM,
)
from .solar_open2 import (  # noqa: F401
    SolarOpen2Config,
    SolarOpen2Model,
    SolarOpen2ForCausalLM,
)
from .laguna import (  # noqa: F401
    LagunaConfig,
    LagunaModel,
    LagunaForCausalLM,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertModel,
    BertForPretraining,
    BertForSequenceClassification,
    bert_base,
    bert_large,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieModel,
    ErnieForPretraining,
    ErnieForSequenceClassification,
    ErnieForTokenClassification,
    ErnieForQuestionAnswering,
    ErnieDataCollator,
    ernie_base,
    ernie_large,
)

__all__ = [
    "GPTConfig",
    "GPTModel",
    "GPTForCausalLM",
    "GPTDecoderLayer",
    "GPTEmbeddings",
    "build_gpt_pipeline_descs",
    "NemotronHConfig",
    "NemotronHModel",
    "NemotronHForCausalLM",
    "SolarOpen2Config",
    "SolarOpen2Model",
    "SolarOpen2ForCausalLM",
    "LagunaConfig",
    "LagunaModel",
    "LagunaForCausalLM",
    "BertConfig",
    "BertModel",
    "BertForPretraining",
    "BertForSequenceClassification",
    "bert_base",
    "bert_large",
    "ErnieConfig",
    "ErnieModel",
    "ErnieForPretraining",
    "ErnieForSequenceClassification",
    "ErnieForTokenClassification",
    "ErnieForQuestionAnswering",
    "ErnieDataCollator",
    "ernie_base",
    "ernie_large",
]
