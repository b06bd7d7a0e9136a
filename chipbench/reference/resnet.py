"""ResNet v1 with bottleneck blocks (He et al., arXiv:1512.03385, Table 1) in
plain float32 ``jax.numpy``: ``lax.conv_general_dilated`` in NCHW, batch
normalisation with the batch's own statistics (training mode), 3x3/2 max
pooling, global average pooling, a dense classifier, softmax cross-entropy.
The stride of a down-sampling block sits on its first 1x1 convolution, as in
the paper. A convolution's bias is optional: the program's bottleneck 1x1
convolutions carry one, which batch normalisation cancels.
"""
import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def conv(x, wb, stride=1, pad=0):
    w, b = wb
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    return y if b is None else y + b[None, :, None, None]


def batch_norm(x, gb, eps=1e-5):
    g, b = gb
    mean = x.mean((0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean((0, 2, 3), keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g[None, :, None, None] \
        + b[None, :, None, None]


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             [(0, 0), (0, 0), (1, 1), (1, 1)])


def bottleneck(x, p, s):
    y = jax.nn.relu(batch_norm(conv(x, p["conv1"], stride=s), p["bn1"]))
    y = jax.nn.relu(batch_norm(conv(y, p["conv2"], pad=1), p["bn2"]))
    y = batch_norm(conv(y, p["conv3"]), p["bn3"])
    if p["down"] is not None:
        x = batch_norm(conv(x, p["down"]["conv"], stride=s), p["down"]["bn"])
    return jax.nn.relu(y + x)


def logits(params, images):
    x = conv(images, params["stem"]["conv"], stride=2, pad=3)
    x = max_pool_3x3_s2(jax.nn.relu(batch_norm(x, params["stem"]["bn"])))
    for i, stage in enumerate(params["stages"]):
        for b, block in enumerate(stage):
            # every stage but the first halves the image in its first block
            x = bottleneck(x, block, 2 if i > 0 and b == 0 else 1)
    x = x.mean((2, 3))
    w, b = params["fc"]
    return jnp.matmul(x, w.T, precision=HIGHEST) + b


def loss(params, images, labels):
    """Mean softmax cross-entropy of (N, 3, H, W) images."""
    logp = jax.nn.log_softmax(logits(params, images), -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1).mean()
