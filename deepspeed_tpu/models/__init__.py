from .transformer import (  # noqa: F401
    RopeTable,
    TransformerConfig,
    TransformerModel,
    make_lm_batch,
)
from .gpt2 import gpt2, gpt2_config  # noqa: F401
from .llama import llama, llama_config  # noqa: F401
from .bloom import bloom, bloom_config  # noqa: F401
from .mixtral import mixtral, mixtral_config  # noqa: F401
from .mellum import mellum, mellum_config  # noqa: F401
from .deepseek import deepseek, deepseek_config  # noqa: F401
from .glm import glm, glm_config  # noqa: F401
from .glm5 import glm5, glm5_config  # noqa: F401
from .minicpm import minicpm, minicpm_config  # noqa: F401
from .ling import ling, ling_config  # noqa: F401
from .brumby import brumby, brumby_config  # noqa: F401
from .cohere import cohere, cohere_config  # noqa: F401
from .keye import keye, keye_config  # noqa: F401
from .qwen3_next import qwen3_next, qwen3_next_config  # noqa: F401
from .lfm2 import lfm2, lfm2_config  # noqa: F401

MODEL_REGISTRY = {
    "gpt2": gpt2,
    "llama": llama,
    "bloom": bloom,
    "mixtral": mixtral,
    "mellum": mellum,
    "deepseek": deepseek,
    "glm": glm,
    "glm5": glm5,
    "minicpm": minicpm,
    "ling": ling,
    "brumby": brumby,
    "cohere": cohere,
    "keye": keye,
    "qwen3_next": qwen3_next,
    "lfm2": lfm2,
}


def get_model(family: str, size: str = None, **overrides):  # noqa: D103
    fn = MODEL_REGISTRY[family]
    return fn(size, **overrides) if size else fn(**overrides)
