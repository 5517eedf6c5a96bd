from .cnn import cifar_cnn, mnist_cnn
from .resnet import resnet, resnet18, resnet34, resnet50
from .transformer import (
    deepseek_v3_lm,
    laguna_lm,
    lfm2_moe_lm,
    qwen3_moe_lm,
    transformer_block,
    transformer_lm,
)
from .vit import vit, vit_base, vit_large, vit_small, vit_tiny

__all__ = [
    "mnist_cnn",
    "cifar_cnn",
    "resnet",
    "resnet18",
    "resnet34",
    "resnet50",
    "transformer_lm",
    "transformer_block",
    "deepseek_v3_lm",
    "qwen3_moe_lm",
    "lfm2_moe_lm",
    "laguna_lm",
    "vit",
    "vit_tiny",
    "vit_small",
    "vit_base",
    "vit_large",
]
