"""Model zoo for the benchmark/example surface.

The reference ships models only as examples (Keras ResNet50 in
`examples/tensorflow2/tensorflow2_synthetic_benchmark.py`, torchvision
resnet50 in `examples/pytorch/pytorch_synthetic_benchmark.py`, MNIST nets in
`examples/keras/keras_mnist.py`) — the models come from the frameworks.
Here they are first-class, TPU-shaped (bfloat16-friendly, static shapes,
MXU-sized matmuls):

- :mod:`.mlp` — MNIST-scale MLP (the keras_mnist example analog);
- :mod:`.resnet` — ResNet-50 v1.5, the flagship benchmark model
  (BASELINE.md: ResNet-50 images/sec/chip);
- :mod:`.transformer` — encoder (BERT-large preset for the Adasum
  BERT-pretraining config) and decoder (GPT preset) with pluggable
  attention: full, ring (sequence-parallel long context), Ulysses; the
  OLMoE preset (RMSNorm, RoPE, QK-norm, untied head, dropless top-k
  expert FFN) through options of the same config, and with them the
  SDAR-30B-A3B (block diffusion, a share of the experts), SmallThinker-21BA3B
  (a layer pattern of windows and positions) and LFM2-8B-A1B presets (gated
  short convolutions among attention layers, a dense FFN before the expert
  layers, sigmoid scores with a selection bias the step keeps;
  Nemotron-3-Super-120B-A12B: layers that are a Mamba-2 mixer, attention or
  experts alone, the experts without a gate on a latent width beside a
  shared expert);
- :mod:`.training` — sharded train-step builders wiring models to the
  ``parallel`` layer and optax.
"""

from .mlp import MLP  # noqa: F401
from .resnet import ResNet, ResNet18, ResNet50, ResNet101  # noqa: F401
from .transformer import (  # noqa: F401
    Transformer,
    TransformerConfig,
    bert_large_config,
    gpt_small_config,
    lfm2_8b_a1b_config,
    moe_stats,
    nemotron_3_super_config,
    olmoe_1b_7b_config,
    sdar_30b_a3b_config,
    smallthinker_21b_a3b_config,
    tiny_config,
)
from .training import TrainState, make_sharded_train_step  # noqa: F401
