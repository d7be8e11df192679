"""BERT-large as ``configs/bert-large.json`` sizes it: the model, its loss,
its data and optimizer from a seed, and its FLOPs per sample from its shapes.

The model is the program's (``horovod_tpu/models/transformer.py``); nothing
else of the program is imported here.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from horovod_tpu.models.transformer import Transformer, TransformerConfig


def matmul_macs(sizes):
    """{name: multiply-adds per sample (one sequence)} of every matrix
    multiplication of the forward pass, from the shapes alone."""
    s, d = sizes["sequence_length"], sizes["hidden_size"]
    layers, ff = sizes["num_hidden_layers"], sizes["intermediate_size"]
    return {
        "qkv": layers * s * d * 3 * d,
        "attention_scores": layers * s * s * d,   # all heads: A * s*s*(d/A)
        "attention_values": layers * s * s * d,
        "attention_out": layers * s * d * d,
        "ffn_in": layers * s * d * ff,
        "ffn_out": layers * s * ff * d,
        "readout": s * d * sizes["vocab_size"],   # tied to the embedding
    }


def flops_per_sample(sizes):
    """Forward + backward of the matrix multiplications, a multiply-add
    counted as 2, nothing recomputed: 2 forward and 4 backward (weight and
    input gradient) per multiply-add.  The embedding lookup is a gather and
    its gradient a scatter; LayerNorm, softmax, GELU and AdamW are
    elementwise; none is counted."""
    return float(6 * sum(matmul_macs(sizes).values()))


class Config:
    def __init__(self, sizes):
        self.sizes = sizes
        self.per_chip_batch = sizes["per_chip_batch"]
        self.first_loss = math.log(sizes["vocab_size"])
        if sizes["sequence_length"] > sizes["max_position_embeddings"]:
            raise ValueError("sequence_length exceeds the position table")
        self.model = Transformer(TransformerConfig(
            vocab_size=sizes["vocab_size"],
            num_layers=sizes["num_hidden_layers"],
            num_heads=sizes["num_attention_heads"],
            d_model=sizes["hidden_size"], d_ff=sizes["intermediate_size"],
            max_len=sizes["max_position_embeddings"], causal=False,
            attention="full", dtype=jnp.bfloat16))

    def _token_shape(self, n):
        return (n, self.sizes["sequence_length"])

    def init(self, key):
        """(params, aux) from a key; meant to run under one ``jax.jit``.
        There is no mutable model state, so aux is empty."""
        v = self.model.init(key, jnp.zeros(self._token_shape(1), jnp.int32))
        return nn.meta.unbox(v["params"]), {}

    def make_batch(self, key):
        return {"tokens": jax.random.randint(
            key, self._token_shape(self.per_chip_batch), 0,
            self.sizes["vocab_size"])}

    def loss(self, params, aux, batch):
        logits = self.model.apply({"params": params}, batch["tokens"])
        # The model hands back bf16 logits; the loss is summed in fp32, as
        # models/training.py::cross_entropy_loss does, so that it resolves
        # more than bf16's 0.06 at ln(vocab) = 10.3.
        return optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), batch["tokens"]).mean(), aux

    def optimizer(self, world):
        return optax.adamw(self.sizes["adamw_learning_rate"])

    def flops_per_sample(self):
        return flops_per_sample(self.sizes)
