"""Model zoo (TPU-native; the reference trains external torch models)."""

from .mlp import MLP
from .resnet import ResNet, ResNet18, ResNet34, ResNet50, ResNet101, ResNet152
from .transformer import TransformerLM, TransformerBlock, MoEMlp
from .session import DecodeSession
from .sambay import SambaY, SambaYConfig
from .brumby import Brumby, BrumbyConfig
from .deepseek import DeepSeek, DeepSeekConfig
from .sdar import SDAR, SDARConfig

__all__ = [
    "MLP",
    "ResNet", "ResNet18", "ResNet34", "ResNet50", "ResNet101", "ResNet152",
    "TransformerLM", "TransformerBlock", "MoEMlp",
    "SambaY", "SambaYConfig", "Brumby", "BrumbyConfig", "DeepSeek", "DeepSeekConfig",
    "SDAR", "SDARConfig", "DecodeSession",
]
