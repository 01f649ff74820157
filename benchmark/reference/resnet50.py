"""Plain reference of the ``resnet50`` configuration: forward pass, loss,
gradients and one momentum step in float32 ``jax.numpy``.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385),
Table 1, 50-layer column: a 7x7/2 stem, 3x3/2 max-pool, bottleneck stages of
(3, 4, 6, 3) blocks at widths (64, 128, 256, 512) x4, global average pool and
a 1000-way classifier; batch normalisation after every convolution, in
training mode (batch statistics, eps 1e-5).  As the program builds it
(``paddle_tpu/models/resnet.py``), the stride of a down-sampling block sits on
its 3x3 convolution ("v1.5", as the common model zoos have it) and not on the
first 1x1 as in the paper; the configuration file lists that departure.

Independent of ``paddle_tpu``: it takes the weights by the names the program
gives them.  The optimizer is plain momentum: v <- mu*v + g, w <- w - lr*v.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}
BASIC = {18: (2, 2, 2, 2), 34: (3, 4, 6, 3)}


def _conv_bn(w, x, name, stride, relu, eps=1e-5):
    k = w[name + "_weights"]
    pad = (k.shape[-1] - 1) // 2
    y = jax.lax.conv_general_dilated(
        x, k, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    mean = jnp.mean(y, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(y - mean), axis=(0, 2, 3), keepdims=True)
    y = (y - mean) * jax.lax.rsqrt(var + eps)
    y = y * w[f"bn_{name}_scale"].reshape(1, -1, 1, 1) \
        + w[f"bn_{name}_offset"].reshape(1, -1, 1, 1)
    return jax.nn.relu(y) if relu else y


def _max_pool_3x3_s2(x):
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), (1, 1), (1, 1)])


def logits(w, img, depth: int):
    x = _conv_bn(w, img, "conv1", 2, True)
    x = _max_pool_3x3_s2(x)
    bottleneck = depth in STAGES
    counts = STAGES[depth] if bottleneck else BASIC[depth]
    for stage, count in enumerate(counts):
        for i in range(count):
            stride = 2 if i == 0 and stage != 0 else 1
            name = f"res{stage + 2}{chr(97 + i)}"
            if bottleneck:
                y = _conv_bn(w, x, name + "_branch2a", 1, True)
                y = _conv_bn(w, y, name + "_branch2b", stride, True)
                y = _conv_bn(w, y, name + "_branch2c", 1, False)
            else:
                y = _conv_bn(w, x, name + "_branch2a", stride, True)
                y = _conv_bn(w, y, name + "_branch2b", 1, False)
            if name + "_branch1_weights" in w:
                x = _conv_bn(w, x, name + "_branch1", stride, False)
            x = jax.nn.relu(x + y)
    x = jnp.mean(x, axis=(2, 3))
    return x @ w["fc_0.w_0"] + w["fc_0.b_0"]


def loss(w, img, label, depth: int):
    """Mean softmax cross-entropy; ``label`` (N,) integer classes."""
    z = logits(w, img, depth)
    logp = jax.nn.log_softmax(z, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, label[:, None], axis=-1))


def two_step_losses(w, img, label, depth: int, lr: float, momentum: float):
    """The loss at the initial weights, and the loss on the same batch after
    one momentum step from zero velocity taken with the reference's own
    gradients: what the program's first two steps should report."""
    with jax.default_matmul_precision("highest"):
        first, grads = jax.value_and_grad(loss)(w, img, label, depth)
        # zero initial velocity: v1 = g, so the first step is w - lr*g
        stepped = {k: w[k] - lr * (momentum * 0.0 + grads[k]) for k in w}
        return first, loss(stepped, img, label, depth)


def trainable(weights: dict) -> dict:
    """The arrays the loss depends on: convolution and classifier weights and
    the batch-norm scales and offsets (moving statistics are not read in
    training mode; optimizer slots and counters are not the model's)."""
    return {k: v for k, v in weights.items()
            if k.endswith(("_weights", "_scale", "_offset"))
            or k in ("fc_0.w_0", "fc_0.b_0")}
