"""Model zoo + flagship models.

Reference analog: ``deeplearning4j-zoo`` (SURVEY §2.4 C15: ZooModel SPI with
LeNet/AlexNet/VGG16/ResNet50/YOLO2/…) plus the BERT workload the reference
runs through TF-import into SameDiff (SURVEY §3.3).

The zoo models build on the conf/MLN/CG stack for API parity; the flagship
``transformer`` is a TPU-first functional model (pure init/forward/loss with
PartitionSpec trees for dp/tp/sp meshes) — the shape a JAX-native user
expects, and the vehicle for the distributed benchmarks.
"""

from .transformer import (
    TransformerConfig,
    forward as transformer_forward,
    init_params as transformer_init,
    loss_fn as transformer_loss,
)
from .zoo import LeNet, SimpleCNN, ZooModel
from .resnet import ResNet50
from .facenet import InceptionResNetV1
from .nasnet import NASNet
from .vgg import VGG16, VGG19
from .text_lstm import TextGenerationLSTM
from .zoo_ext import AlexNet, Darknet19, SqueezeNet, UNet, Xception
from .kimi_k2 import KimiK2Config
from .keye_vl import KeyeVLConfig  # served only, as kimi_k2: no loss, no backward
from .trinity import TrinityConfig  # served only: the forward of its windowed kernels
from .vae import VariationalAutoencoder
from .yolo import TinyYOLO, Yolo2OutputLayer

__all__ = [
    "AlexNet", "Darknet19", "SqueezeNet", "UNet", "Xception",
    "KimiK2Config",
    "KeyeVLConfig",
    "TrinityConfig",
    "VariationalAutoencoder", "TinyYOLO", "Yolo2OutputLayer",
    "TransformerConfig",
    "transformer_forward",
    "transformer_init",
    "transformer_loss",
    "ZooModel",
    "LeNet",
    "SimpleCNN",
    "ResNet50",
    "VGG16",
    "VGG19",
    "InceptionResNetV1",
    "NASNet",
    "TextGenerationLSTM",
]
