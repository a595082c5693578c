"""Model zoo.

Reference: python/paddle/vision/models (ResNet/VGG/MobileNet/... listing,
SURVEY §2.3) for vision; PaddleNLP entrypoints (BASELINE.md configs) for the
language flagship. Everything is built on paddle_tpu.nn layers and the
distributed mpu layers, so every model is single-chip AND hybrid-parallel
capable from the same code.
"""
from .gpt import (  # noqa: F401
    GPTConfig, GPTModel, GPTForCausalLM, GPTPretrainingCriterion,
    gpt_config, PRESETS as GPT_PRESETS,
)
from .pangu_moe import PanguMoEConfig, PanguMoEForCausalLM  # noqa: F401
from .gpt_stacked import (  # noqa: F401
    GPTStackedForCausalLM,
)
from .bert import (  # noqa: F401
    BertConfig, BertModel, BertForMaskedLM, BertForSequenceClassification,
    BertForPretraining, bert_config,
)
from .vit import (  # noqa: F401
    ViTConfig, VisionTransformer, vit_config,
)
from .ernie import (  # noqa: F401
    ErnieConfig, ErnieModel, ErnieForSequenceClassification,
    ErnieForMaskedLM, ernie_config,
)
