from tpufw.models.deepseek import (  # noqa: F401
    DEEPSEEK_CONFIGS,
    Deepseek,
    DeepseekConfig,
)
from tpufw.models.falcon_h1 import (  # noqa: F401
    FALCON_H1_CONFIGS,
    FalconH1,
    FalconH1Config,
)
from tpufw.models.gemma import (  # noqa: F401
    GEMMA_CONFIGS,
    Gemma,
    GemmaConfig,
)
from tpufw.models.laguna import (  # noqa: F401
    LAGUNA_CONFIGS,
    Laguna,
    LagunaConfig,
)
from tpufw.models.llama import (  # noqa: F401
    Llama,
    LlamaConfig,
    LLAMA_CONFIGS,
    RopeScaling,
    unstack_layer_params,
)
from tpufw.models.mixtral import (  # noqa: F401
    MIXTRAL_CONFIGS,
    Mixtral,
    MixtralConfig,
    MoEMLP,
)
from tpufw.models.olmo_hybrid import (  # noqa: F401
    OLMO_HYBRID_CONFIGS,
    OlmoHybrid,
    OlmoHybridConfig,
)
from tpufw.models.phi4flash import (  # noqa: F401
    PHI4FLASH_CONFIGS,
    Phi4Flash,
    Phi4FlashConfig,
)
from tpufw.models.resnet import ResNet, ResNetConfig, resnet50  # noqa: F401
from tpufw.models.solar_open2 import (  # noqa: F401
    SOLAR_OPEN2_CONFIGS,
    SolarOpen2,
    SolarOpen2Config,
)
from tpufw.models.vit import (  # noqa: F401
    VIT_CONFIGS,
    ViT,
    ViTConfig,
    vit_b16,
)
from tpufw.models.lora import (  # noqa: F401
    has_lora,
    lora_mask,
    merge_lora,
)


def model_for_config(cfg):
    """Model class instance for a config dataclass — the ONE
    config->architecture dispatch (serving, eval tools)."""
    from tpufw.models.deepseek import DeepseekConfig
    from tpufw.models.gemma import GemmaConfig
    from tpufw.models.mixtral import MixtralConfig
    from tpufw.models.resnet import ResNetConfig

    if isinstance(cfg, ResNetConfig):
        raise ValueError(
            "model_for_config covers the LM families; vision runs use "
            "tpufw.train.VisionTrainer / workloads.train_resnet"
        )
    if isinstance(cfg, DeepseekConfig):
        return Deepseek(cfg)
    if isinstance(cfg, SolarOpen2Config):  # a LlamaConfig too: first
        return SolarOpen2(cfg)
    if isinstance(cfg, LagunaConfig):  # likewise
        return Laguna(cfg)
    if isinstance(cfg, FalconH1Config):  # likewise
        return FalconH1(cfg)
    if isinstance(cfg, OlmoHybridConfig):  # likewise
        return OlmoHybrid(cfg)
    if isinstance(cfg, Phi4FlashConfig):  # likewise
        return Phi4Flash(cfg)
    if isinstance(cfg, MixtralConfig):
        return Mixtral(cfg)
    if isinstance(cfg, GemmaConfig):
        return Gemma(cfg)
    if isinstance(cfg, LlamaConfig):
        return Llama(cfg)
    raise TypeError(f"unknown model config type {type(cfg).__name__}")
