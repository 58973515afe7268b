"""Flagship model families built on the framework (GPT, BERT/ERNIE;
vision detection configs follow the same pattern)."""
from .bert import (  # noqa: F401
    BERT_CONFIGS,
    BertConfig,
    BertForPretraining,
    BertModel,
    BertPretrainingCriterion,
    ErnieConfig,
    ErnieForPretraining,
    ErnieModel,
    bert_config,
    build_bert,
    build_ernie,
)
from .decoder import (  # noqa: F401
    DECODER_CONFIGS,
    DecoderConfig,
    DecoderForCausalLM,
    DecoderModel,
    build_decoder,
    decoder_config,
)
from .gpt import (  # noqa: F401
    GPT_CONFIGS,
    GPTConfig,
    GPTDecoderLayer,
    GPTEmbeddings,
    GPTForPretraining,
    GPTMoEMLP,
    GPTMoEPretrainingCriterion,
    GPTModel,
    GPTPretrainingCriterion,
    build_gpt,
    gpt_config,
    gpt_pipeline_descs,
    gpt_num_params,
    gpt_train_flops_per_token,
)
