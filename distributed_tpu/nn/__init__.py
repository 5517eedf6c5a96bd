from .attention import (
    GroupedQueryAttention,
    LatentAttention,
    MultiHeadAttention,
    PositionalEmbedding,
)
from .augment import RandomCrop, RandomFlip
from .moe import DroplessMoE, MoE
from .pipeline import PipelinedBlocks
from .scan import ScannedBlocks
from .remat import Remat
from .core import Lambda, Layer, Residual, Sequential, TiedSequential
from .layers import (
    Activation,
    AvgPool2D,
    BatchNorm,
    Conv2D,
    Dense,
    Dropout,
    Embedding,
    Flatten,
    GatedMLP,
    GlobalAvgPool2D,
    LayerNorm,
    MaxPool2D,
    RMSNorm,
    ShortConv,
    SpaceToDepth,
    TiedHead,
)

__all__ = [
    "Layer",
    "Sequential",
    "TiedSequential",
    "Residual",
    "Lambda",
    "Conv2D",
    "Dense",
    "Flatten",
    "Activation",
    "MaxPool2D",
    "AvgPool2D",
    "GlobalAvgPool2D",
    "BatchNorm",
    "LayerNorm",
    "Dropout",
    "Embedding",
    "SpaceToDepth",
    "RandomFlip",
    "RandomCrop",
    "MultiHeadAttention",
    "LatentAttention",
    "GroupedQueryAttention",
    "MoE",
    "DroplessMoE",
    "RMSNorm",
    "GatedMLP",
    "ShortConv",
    "TiedHead",
    "PipelinedBlocks",
    "ScannedBlocks",
    "PositionalEmbedding",
    "Remat",
]
