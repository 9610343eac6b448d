"""Model zoo: flax modules for every workload family the reference ships.

Reference inventory (SURVEY.md §2.4, fedstellar/learning/pytorch/*):
MNIST MLP/CNN, FEMNIST CNN, CIFAR10 ResNet9/18/34/50 + two MobileNets,
SYSCALL MLP/Autoencoder/One-class-SVM, WADI MLP — plus ViT-Tiny for the
stretch config in BASELINE.json and four sparse-expert language models
as frozen bases under adapters: Ling-3.0-flash (linear and latent
attention), Laguna-S-2.1 (window and full grouped-query attention),
LFM2-8B-A1B (gated short convolutions among grouped-query attention, a
tied head), Kimi-Linear-48B-A3B (Kimi Delta Attention with an
unbounded decay among NoPE latent attention) and Qwen3-Next-80B-A3B
(Gated DeltaNet with a decay a head among gated attention).

TPU-first design notes:
- Normalization is **GroupNorm**, not BatchNorm: batch statistics are
  known-pathological under non-IID federated data, and GroupNorm keeps
  the model a *pure* param pytree (no mutable batch_stats collection to
  gossip separately), which keeps every federated collective a single
  fixed-shape tree op.
- All modules take ``dtype`` (compute) and ``param_dtype`` so the MXU
  path runs bfloat16 with float32 params by default.
"""

from p2pfl_tpu.models.base import get_model, list_models, register_model
from p2pfl_tpu.models.mlp import MLP, MNISTModelMLP, SyscallModelMLP, WADIModelMLP
from p2pfl_tpu.models.cnn import FEMNISTModelCNN, MNISTModelCNN
from p2pfl_tpu.models.resnet import CIFAR10ModelResNet, ResNet
from p2pfl_tpu.models.mobilenet import FasterMobileNet, SimpleMobileNet
from p2pfl_tpu.models.syscall import SyscallModelAutoencoder, SyscallModelOneClassSVM
from p2pfl_tpu.models.vit import ViT
from p2pfl_tpu.models.ling import LingLM
from p2pfl_tpu.models.laguna import LagunaLM
from p2pfl_tpu.models.lfm2 import Lfm2LM
from p2pfl_tpu.models.kimi_linear import KimiLinearLM
from p2pfl_tpu.models.qwen3_next import Qwen3NextLM

__all__ = [
    "get_model",
    "list_models",
    "register_model",
    "MLP",
    "MNISTModelMLP",
    "SyscallModelMLP",
    "WADIModelMLP",
    "MNISTModelCNN",
    "FEMNISTModelCNN",
    "ResNet",
    "CIFAR10ModelResNet",
    "FasterMobileNet",
    "SimpleMobileNet",
    "SyscallModelAutoencoder",
    "SyscallModelOneClassSVM",
    "ViT",
    "LingLM",
    "LagunaLM",
    "Lfm2LM",
    "KimiLinearLM",
    "Qwen3NextLM",
]
