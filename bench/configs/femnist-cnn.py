"""Plain reference of femnist-cnn, the BFLC paper's global model as the repo
builds it (arXiv:2004.00773 §VI; a 3x3 CNN in AlexNet's role on FEMNIST),
and its operation count.

Straightforward ``jax.numpy``: two SAME 3x3 convolutions of width w and 2w,
each followed by ReLU and 2x2 max pooling, then a 128-wide ReLU layer and a
62-way linear head over the NHWC-flattened 7x7x2w map.  The weights are made
here from the seed, as the program's initialiser describes them: He-normal
convolutions and first dense layer, zero biases, and a zero output layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NUM_CLASSES = 62
IMAGE = (28, 28, 1)


def init_params(key, width: int, num_classes: int = NUM_CLASSES):
    """The model's weights from ``key``, made on the device in one call."""

    def make(key):
        k1, k2, k3 = jax.random.split(key, 3)

        def he(k, shape, fan_in):
            return jax.random.normal(k, shape, jnp.float32) * math.sqrt(2.0 / fan_in)

        w = width
        return {
            "conv1": {"w": he(k1, (3, 3, 1, w), 9), "b": jnp.zeros((w,))},
            "conv2": {"w": he(k2, (3, 3, w, 2 * w), 9 * w),
                      "b": jnp.zeros((2 * w,))},
            "fc1": {"w": he(k3, (7 * 7 * 2 * w, 128), 7 * 7 * 2 * w),
                    "b": jnp.zeros((128,))},
            "fc2": {"w": jnp.zeros((128, num_classes)),
                    "b": jnp.zeros((num_classes,))},
        }

    return jax.jit(make)(key)


def _conv(x, p):
    y = jax.lax.conv_general_dilated(
        x, p["w"].astype(x.dtype), (1, 1), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"].astype(x.dtype)


def _pool(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                 (1, 2, 2, 1), "VALID")


def forward(params, images):
    """(B, 28, 28, 1) -> (B, 62) logits, in the dtype of ``images``."""
    x = _pool(jax.nn.relu(_conv(images, params["conv1"])))
    x = _pool(jax.nn.relu(_conv(x, params["conv2"])))
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(x @ params["fc1"]["w"].astype(x.dtype)
                    + params["fc1"]["b"].astype(x.dtype))
    return x @ params["fc2"]["w"].astype(x.dtype) + params["fc2"]["b"].astype(x.dtype)


def loss(params, images, labels):
    logp = jax.nn.log_softmax(forward(params, images))
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def accuracy(params, images, labels):
    return jnp.mean(jnp.argmax(forward(params, images), axis=-1) == labels)


def forward_flops_per_image(width: int, num_classes: int = NUM_CLASSES) -> int:
    """Multiply-add FLOPs (2 per MAC) of one image's forward pass."""
    w = width
    conv1 = 2 * 28 * 28 * (3 * 3 * 1) * w
    conv2 = 2 * 14 * 14 * (3 * 3 * w) * (2 * w)
    fc1 = 2 * (7 * 7 * 2 * w) * 128
    fc2 = 2 * 128 * num_classes
    return conv1 + conv2 + fc1 + fc2


def train_flops_per_image(width: int, num_classes: int = NUM_CLASSES) -> int:
    """Forward plus backward: the backward pass takes twice the forward's."""
    return 3 * forward_flops_per_image(width, num_classes)
