"""Adapter: ResNet v1 for ImageNet (He et al. 2015) trained through
paddle_tpu.models.resnet.build_resnet_train_program.  See
transformer_wmt.py for what an adapter is.
"""

import numpy as np

# |program loss - reference loss| on the sampled images: bf16 AMP convs
# against float32 "highest", batch-norm statistics over the same 8 images
# on both sides.  Dividing by the variance of 8 x 7 x 7 values in the last
# stage amplifies bf16 rounding: on the chip at full width the difference
# was 1e-4 to 2.5e-2 in 13 runs of as many seeds (my chip runs, PR 22) on a
# loss of ~3.6 after the window, so the tolerance is 4 times the largest.
# A wrong stride, a missing shortcut or inference-mode batch norm moves the
# loss by several tenths or more.
TOLERANCE = 1e-1

BLOCKS = {50: [3, 4, 6, 3], 101: [3, 4, 23, 3], 152: [3, 8, 36, 3]}


def build(cfg, work, mesh=None, forward_only=False):
    """The builder's train program; forward_only is the same network and
    loss built from the model's own public function, without backward or
    optimizer (the builder has no such switch).  Both are built under a
    fresh unique_name guard, so the generated parameter names agree and the
    weights are shared through the scope.  Batch norm stays in training
    mode (batch statistics), which is what the train step computes."""
    import paddle_tpu as fluid
    from paddle_tpu import layers, unique_name
    from paddle_tpu.models import resnet

    if mesh is not None:
        raise ValueError("the resnet builder takes no mesh")
    m, train = cfg["model"], cfg["train"]
    shape = (3, int(m["image_size"]), int(m["image_size"]))
    if not forward_only:
        with unique_name.guard():
            main, startup, feeds, fetches = resnet.build_resnet_train_program(
                batch_size=int(work["batch"]), image_shape=shape,
                class_dim=int(m["class_dim"]), depth=int(m["depth"]),
                lr=float(train["learning_rate"]), optimizer="momentum",
                use_bf16=bool(train["use_bf16"]))
        return {"main": main, "startup": startup, "feeds": feeds,
                "loss": fetches[0]}
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        img = layers.data("image", shape=list(shape), dtype="float32")
        label = layers.data("label", shape=[1], dtype="int64")
        predict = resnet.resnet_imagenet(img, int(m["class_dim"]),
                                         int(m["depth"]))
        loss = layers.mean(layers.cross_entropy(input=predict, label=label))
        if train["use_bf16"]:
            from paddle_tpu.transpiler.pass_registry import apply_pass

            apply_pass(main, "bf16_amp_pass")
    return {"main": main, "startup": startup, "feeds": ["image", "label"],
            "loss": loss}


def make_batch(cfg, work, seed):
    """float32 NCHW images (standard normal, as normalised pixels are) and
    uniform labels, from the host: 77 MB per batch of 128."""
    m = cfg["model"]
    b, size = int(work["batch"]), int(m["image_size"])
    rng = np.random.default_rng(seed)
    return {
        "image": rng.standard_normal((b, 3, size, size), dtype="float32"),
        "label": rng.integers(0, m["class_dim"], (b, 1)).astype("int64"),
    }


def work_units(batch):
    """Images."""
    return float(batch["image"].shape[0])


def _out(h, k, stride, pad):
    return (h + 2 * pad - k) // stride + 1


def _convs(m):
    """(c_in, c_out, kernel, stride, padding, is_shortcut, h_in) of every
    convolution in the order the architecture creates them."""
    size = int(m["image_size"])
    out = [(3, 64, 7, 2, 3, False, size)]
    h = _out(_out(size, 7, 2, 3), 3, 2, 1)  # stem, then the 3x3/2 max pool
    c_in = 64
    for stage, count in enumerate(BLOCKS[int(m["depth"])]):
        width = 64 * 2 ** stage
        for i in range(count):
            stride = 2 if (i == 0 and stage > 0) else 1
            if c_in != width * 4:
                out.append((c_in, width * 4, 1, stride, 0, True, h))
            out.append((c_in, width, 1, stride, 0, False, h))
            h = _out(h, 1, stride, 0)
            out += [(width, width, 3, 1, 1, False, h),
                    (width, width * 4, 1, 1, 0, False, h)]
            c_in = width * 4
    return out


def model_flops(cfg, work):
    """Convolution and classifier multiply-adds of the forward pass from
    the architecture's shapes, times 2 (operations) times 3 (forward +
    backward).  The first convolution needs no input gradient, which this
    convention, like utils.flops.program_flops, does not subtract (1% of
    the total)."""
    m = cfg["model"]
    macs = sum(c_in * c_out * k * k * _out(h, k, s, p) ** 2
               for c_in, c_out, k, s, p, _, h in _convs(m))
    macs += 2048 * int(m["class_dim"])
    return 3.0 * 2.0 * int(work["batch"]) * macs


# --------------------------------------------------------------------------
# plain reference: bottleneck ResNet v1, NCHW, training-mode batch norm
# --------------------------------------------------------------------------
def reference_loss(cfg, params, batch):
    import jax
    import jax.numpy as jnp

    weights = [jnp.asarray(v, jnp.float32) for _, v in params]
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda w, b: _forward(cfg["model"], w, b))(
            weights, batch))


def _forward(m, weights, batch):
    import jax
    import jax.numpy as jnp

    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def conv_bn(x, c_in, c_out, k, stride, pad, relu):
        w = take(c_out, c_in, k, k)
        g, b = take(c_out), take(c_out)
        take(c_out), take(c_out)  # moving mean / variance: unused in training
        y = jax.lax.conv_general_dilated(
            x, w, (stride, stride), [(pad, pad), (pad, pad)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        mu = y.mean((0, 2, 3), keepdims=True)
        var = ((y - mu) ** 2).mean((0, 2, 3), keepdims=True)
        y = (y - mu) / jnp.sqrt(var + 1e-5) * g[None, :, None, None] \
            + b[None, :, None, None]
        return jax.nn.relu(y) if relu else y

    convs = iter(_convs(m))
    x = jnp.asarray(batch["image"])
    c_in, c_out, k, s, p = next(convs)[:5]
    x = conv_bn(x, c_in, c_out, k, s, p, True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        ((0, 0), (0, 0), (1, 1), (1, 1)))
    spec = next(convs, None)
    while spec is not None:
        short = x
        if spec[5]:
            short = conv_bn(x, *spec[:5], False)
            spec = next(convs)
        y = conv_bn(x, *spec[:5], True)
        y = conv_bn(y, *next(convs)[:5], True)
        y = conv_bn(y, *next(convs)[:5], False)
        x = jax.nn.relu(short + y)
        spec = next(convs, None)
    x = x.mean((2, 3))
    n_cls = int(m["class_dim"])
    probs = jax.nn.softmax(x @ take(x.shape[1], n_cls) + take(n_cls), -1)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    picked = jnp.take_along_axis(
        probs, jnp.asarray(batch["label"]).reshape(-1, 1), -1)
    return -jnp.log(jnp.clip(picked, 1e-20, None)).mean()
