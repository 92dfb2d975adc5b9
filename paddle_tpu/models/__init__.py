"""Model zoo: the config-ladder families (BASELINE.md).

ResNet/VGG/MobileNet live in paddle_tpu.vision.models; this package holds
the LLM/diffusion families.
"""
from .llama import LlamaConfig, LlamaModel, LlamaForCausalLM, llama_tiny, llama_3_8b  # noqa: F401
from .ernie import (ErnieConfig, ErnieModel, ErnieForMaskedLM,  # noqa: F401
                    ErnieForPretraining, ErnieForSequenceClassification,
                    ErnieForTokenClassification, ernie_tiny, ernie_3_base)
from .dit import (DiTConfig, DiT, GaussianDiffusion, dit_tiny,  # noqa: F401
                  dit_s_2, dit_xl_2)
from .unet import UNetConfig, UNet2DModel, unet_tiny  # noqa: F401
from .generation import jit_generate  # noqa: F401
from .qwen2_moe import (Qwen2MoeConfig, Qwen2MoeForCausalLM,  # noqa: F401
                        qwen2_moe_tiny, qwen2_moe_a14b)


# Kimi-K2 (models/kimi_k2.py) is imported when it is asked for, not with
# the package: `import paddle_tpu` is part of every program's set-up.
_LAZY = {"KimiK2Config": "kimi_k2", "KimiK2ForCausalLM": "kimi_k2",
         "kimi_k2_tiny": "kimi_k2"}


def __getattr__(name):
    if name in _LAZY:
        import importlib
        return getattr(importlib.import_module(f".{_LAZY[name]}", __name__),
                       name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
