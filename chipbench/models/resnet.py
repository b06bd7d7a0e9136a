"""ResNet v1 (bottleneck) training as bench.py's bench_resnet builds it: the
model zoo's network, softmax cross-entropy, SGD with momentum."""
import types

import numpy as onp

from ..reference import resnet as reference


def conv_shapes(config):
    """Every convolution and the classifier as (out_hw, k, c_in, c_out),
    walked from the configuration: the stem, then per stage its bottleneck
    blocks (1x1 carrying the stride, 3x3, 1x1, and a strided 1x1 projection
    where the shape changes)."""
    hw = config["image_size"] // 2
    out = [(hw, 7, 3, config["stem_channels"])]
    hw //= 2                                     # 3x3/2 max pooling
    c_in = config["stem_channels"]
    for i, (blocks, width) in enumerate(zip(config["layers"],
                                            config["stage_channels"])):
        for b in range(blocks):
            stride = 2 if (i > 0 and b == 0) else 1
            hw //= stride
            mid = width // 4
            out += [(hw, 1, c_in, mid), (hw, 3, mid, mid), (hw, 1, mid, width)]
            if c_in != width:
                out.append((hw, 1, c_in, width))
            c_in = width
    out.append((1, 1, c_in, config["classes"]))
    return out


def flops_per_sample(config, cell):
    """Forward + backward FLOPs one image requires: 2 per multiply-add of
    every convolution and the classifier, backward twice the forward, nothing
    recomputed. Batch normalisation, pooling and activations are not counted
    (under 1%)."""
    macs = sum(hw * hw * k * k * c_in * c_out
               for hw, k, c_in, c_out in conv_shapes(config))
    return 3.0 * 2.0 * macs


def build_train(config, cell, seed, context):
    import mxnet_tpu as mx
    from mxnet_tpu.gluon import loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(seed)
    onp.random.seed(seed)
    size, classes = config["image_size"], config["classes"]
    with context:          # initialised where it will train, not on the host
        net = vision.get_model(config["model_zoo_name"], classes=classes)
        net.initialize(mx.init.Xavier())
        net(mx.nd.zeros((1, 3, size, size), dtype="float32"))     # shapes

    def make_batches(key, k, samples):
        """K micro-batches: uniform images in the compute type, labels as
        the float class ids the loss takes."""
        import jax
        import jax.numpy as jnp
        kx, ky = jax.random.split(key)
        x = jax.random.uniform(kx, (k, samples, 3, size, size),
                               jnp.dtype(cell["compute_dtype"] or "float32"))
        y = jax.random.randint(ky, (k, samples), 0, classes, jnp.int32)
        return x, y.astype(jnp.float32)

    def reference_loss(batches):
        import jax
        import jax.numpy as jnp
        x, y = batches
        return float(jax.jit(reference.loss)(
            reference_params(net), x[0].astype(jnp.float32),
            y[0].astype(jnp.int32)))

    return types.SimpleNamespace(
        block=net, loss=gloss.SoftmaxCrossEntropyLoss(),
        optimizer=mx.optimizer.SGD(learning_rate=cell["learning_rate"],
                                   momentum=cell["momentum"]),
        extra_specs=(), compute_dtype=cell["compute_dtype"],
        make_batches=make_batches, reference_loss=reference_loss,
        rates={"images_per_s": 1})


def _f32(param):
    import jax.numpy as jnp
    return jnp.asarray(param.data().data, jnp.float32)


def _conv(layer):
    return (_f32(layer.weight),
            _f32(layer.bias) if layer.bias is not None else None)


def _bn(layer):
    return (_f32(layer.gamma), _f32(layer.beta))


def reference_params(net):
    """The reference's parameter tree, read off the model zoo network's
    blocks in order: stem (conv, bn, relu, pool), four stages, pooling."""
    feats = list(net.features._children.values())
    tree = {"stem": {"conv": _conv(feats[0]), "bn": _bn(feats[1])},
            "stages": [], "fc": (_f32(net.output.weight),
                                 _f32(net.output.bias))}
    for stage in feats[4:8]:
        blocks = []
        for blk in stage._children.values():
            body = list(blk.body._children.values())
            down = None
            if blk.downsample is not None:
                d = list(blk.downsample._children.values())
                down = {"conv": _conv(d[0]), "bn": _bn(d[1])}
            blocks.append({"conv1": _conv(body[0]), "bn1": _bn(body[1]),
                           "conv2": _conv(body[3]), "bn2": _bn(body[4]),
                           "conv3": _conv(body[6]), "bn3": _bn(body[7]),
                           "down": down})
        tree["stages"].append(blocks)
    return tree
