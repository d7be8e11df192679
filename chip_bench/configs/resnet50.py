"""ResNet-50 as ``configs/resnet50.json`` sizes it: the model, its loss, its
data and optimizer from a seed, and its FLOPs per sample from its shapes.

The model is the program's (``horovod_tpu/models/resnet.py``); nothing else
of the program is imported here, so ``chip_bench/reference.py`` can step it
plainly and the step builders under test can be held to that.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.resnet import BottleneckBlock, ResNet


def conv_macs(sizes):
    """[(name, multiply-adds per sample)] of every convolution and of the
    classifier, in forward order, from the shapes alone.

    Stem: 7x7 stride 2, then a 3x3 stride-2 max pool.  Stage i has
    ``stage_sizes[i]`` bottlenecks of width w = num_filters * 2**i: 1x1 (in ->
    w), 3x3 (w -> w, carrying the stage's stride in its first block), 1x1
    (w -> 4w), and a 1x1 projection of the residual where the shape changes.
    """
    hw = sizes["image_size"] // 2          # after the stride-2 stem
    f = sizes["num_filters"]
    x = sizes["bottleneck_expansion"]
    macs = [("conv_init", hw * hw * 7 * 7 * sizes["image_channels"] * f)]
    hw //= 2                               # after the max pool
    cin = f
    for i, blocks in enumerate(sizes["stage_sizes"]):
        w = f * 2 ** i
        for j in range(blocks):
            stride = 2 if i > 0 and j == 0 else 1
            out = hw // stride
            macs.append((f"s{i}b{j}.conv1", hw * hw * cin * w))
            macs.append((f"s{i}b{j}.conv2", out * out * 9 * w * w))
            macs.append((f"s{i}b{j}.conv3", out * out * w * w * x))
            if cin != w * x or stride != 1:
                macs.append((f"s{i}b{j}.proj", out * out * cin * w * x))
            cin, hw = w * x, out
    macs.append(("classifier", cin * sizes["num_classes"]))
    return macs


def flops_per_sample(sizes):
    """Forward + backward of the convolutions and the classifier, a
    multiply-add counted as 2, nothing recomputed.  The backward pass costs a
    weight gradient and an input gradient per layer (4 per multiply-add),
    except the stem, whose input is the image and needs none (2).
    Elementwise work (BatchNorm, ReLU, the optimizer) is not counted: this is
    the work of the model, what an MFU divides by the peak."""
    macs = conv_macs(sizes)
    return float(6 * sum(m for _, m in macs) - 2 * macs[0][1])


class Config:
    def __init__(self, sizes):
        self.sizes = sizes
        self.per_chip_batch = sizes["per_chip_batch"]
        # A fresh model predicts every class alike.
        self.first_loss = math.log(sizes["num_classes"])
        if sizes["bottleneck_expansion"] != 4:
            raise ValueError("models/resnet.py fixes the expansion at 4")
        self.model = ResNet(stage_sizes=sizes["stage_sizes"],
                            block_cls=BottleneckBlock,
                            num_classes=sizes["num_classes"],
                            num_filters=sizes["num_filters"],
                            dtype=jnp.bfloat16)

    def _image_shape(self, n):
        s = self.sizes
        return (n, s["image_size"], s["image_size"], s["image_channels"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``."""
        v = self.model.init(key, jnp.zeros(self._image_shape(1),
                                           jnp.bfloat16), train=True)
        return v["params"], v["batch_stats"]

    def make_batch(self, key):
        kx, ky = jax.random.split(key)
        n = self.per_chip_batch
        return {"x": jax.random.uniform(kx, self._image_shape(n),
                                        jnp.bfloat16),
                "y": jax.random.randint(ky, (n,), 0,
                                        self.sizes["num_classes"])}

    def loss(self, params, aux, batch):
        """-> (mean cross-entropy, new BatchNorm statistics)."""
        logits, updates = self.model.apply(
            {"params": params, "batch_stats": aux}, batch["x"], train=True,
            mutable=["batch_stats"])
        one_hot = jax.nn.one_hot(batch["y"], self.sizes["num_classes"])
        return (optax.softmax_cross_entropy(logits, one_hot).mean(),
                updates["batch_stats"])

    def optimizer(self, world):
        return optax.sgd(self.sizes["sgd_learning_rate"] * world,
                         momentum=self.sizes["sgd_momentum"])

    def flops_per_sample(self):
        return flops_per_sample(self.sizes)
