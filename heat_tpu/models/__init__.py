"""Model zoo (TPU-native; the reference trains external torch models)."""

from .mlp import MLP
from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from .transformer import TransformerLM, TransformerBlock, MoEMlp
from .sambay import SambaY, SambaYConfig, DecodeSession

__all__ = [
    "MLP",
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "TransformerLM", "TransformerBlock", "MoEMlp",
    "SambaY", "SambaYConfig", "DecodeSession",
]
