"""Neural-net op lowerings: conv, pool, norms, dropout, rnn blocks.

TPU-native replacements for the reference's cudnn/mkldnn-backed kernels
(``operators/conv_op.*``, ``pool_op.*``, ``batch_norm_op.*``,
``layer_norm_op.*``, ``dropout_op.*``, ``lstm_op.*``, ``gru_op.*``): convs
map to ``lax.conv_general_dilated`` (MXU), recurrences to ``lax.scan``
(compiled control flow instead of the reference's per-step StepScopes
interpreter), and gradients fall out of ``jax.vjp`` — including scan-based
RNNs.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import current_microbatch_rows, register
from .common import jdt


def _pair(v):
    if isinstance(v, (list, tuple)):
        return list(v)
    return [v, v]


# ---------------------------------------------------------------------------
# convolution family
# ---------------------------------------------------------------------------
def _conv2d_impl(x, w, attrs, groups=None):
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = groups if groups is not None else attrs.get("groups", 1) or 1
    fmt = attrs.get("data_format", "NCHW")  # nhwc_layout_pass sets NHWC
    pad = [(paddings[0], paddings[0]), (paddings[1], paddings[1])]
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=pad,
        rhs_dilation=dilations,
        dimension_numbers=(fmt, "OIHW", fmt),
        feature_group_count=groups,
        preferred_element_type=jnp.float32 if x.dtype == jnp.float32 else None,
    )


def _bias_shape(attrs, ndim=4):
    shape = [1] * ndim
    shape[1 if attrs.get("data_format", "NCHW") == "NCHW" else ndim - 1] = -1
    return shape


@register("conv2d")
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    out = _conv2d_impl(x, w, attrs)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(_bias_shape(attrs))
    if attrs.get("fuse_relu"):  # fuse_relu_into_conv_pass epilogue
        out = jnp.maximum(out, 0)
    return {"Output": [out]}


@register("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    ch = x.shape[1 if attrs.get("data_format", "NCHW") == "NCHW" else -1]
    out = _conv2d_impl(x, w, attrs, groups=ch)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(_bias_shape(attrs))
    return {"Output": [out]}


@register("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    # paddle semantics: out = (H-1)*s - 2p + k_eff.  jax applies `padding`
    # to the stride-dilated input of a plain conv with the flipped kernel,
    # so each side needs k_eff - 1 - p
    k_eff = [dilations[i] * (w.shape[2 + i] - 1) + 1 for i in range(2)]
    pad = [(k_eff[i] - 1 - paddings[i],) * 2 for i in range(2)]

    # w layout: [in_c, out_c/groups, kh, kw] (paddle conv_transpose filter);
    # with transpose_kernel=True jax SWAPS the I/O labels, so the in_c dim
    # must be labeled 'O' (it is the contraction side of the transposed
    # conv); lax.conv_transpose has no group support, so groups unroll
    def one(xg, wg):
        return jax.lax.conv_transpose(
            xg,
            wg,
            strides=strides,
            padding=pad,
            rhs_dilation=dilations,
            dimension_numbers=("NCHW", "OIHW", "NCHW"),
            transpose_kernel=True,
        )

    if groups == 1:
        out = one(x, w)
    else:
        cin = x.shape[1] // groups
        outs = [
            one(x[:, g * cin : (g + 1) * cin], w[g * cin : (g + 1) * cin])
            for g in range(groups)
        ]
        out = jnp.concatenate(outs, axis=1)
    return {"Output": [out]}


@register("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = attrs.get("strides", [1, 1, 1])
    paddings = attrs.get("paddings", [0, 0, 0])
    dilations = attrs.get("dilations", [1, 1, 1])
    pad = [(p, p) for p in paddings]
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=pad,
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=attrs.get("groups", 1) or 1,
    )
    return {"Output": [out]}


# ---------------------------------------------------------------------------
# pooling (operators/pool_op.*)
# ---------------------------------------------------------------------------
@register("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [1, 1]))
    paddings = _pair(attrs.get("paddings", [0, 0]))
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    sp = (1, 2) if nhwc else (2, 3)  # spatial axes
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and list(
        attrs.get("ksize")
    ) == [1, 1]:
        if ptype == "max":
            out = jnp.max(x, axis=sp, keepdims=True)
        else:
            out = jnp.mean(x, axis=sp, keepdims=True)
        return {"Out": [out]}

    def _full(h, w_):
        # place the spatial (h, w) values on the spatial axes, 1 elsewhere
        full = [1, 1, 1, 1]
        full[sp[0]], full[sp[1]] = h, w_
        return tuple(full)

    window = _full(ksize[0], ksize[1])
    strides_full = _full(strides[0], strides[1])
    extra = [0, 0]
    if attrs.get("ceil_mode", False):
        # pad right/bottom so the window count rounds up
        for i, (dim, k, s, p) in enumerate(
            zip((x.shape[sp[0]], x.shape[sp[1]]), ksize, strides, paddings)
        ):
            total = dim + 2 * p
            rem = (total - k) % s
            extra[i] = (s - rem) % s if rem else 0
    pads = [(0, 0)] * 4
    pads[sp[0]] = (paddings[0], paddings[0] + extra[0])
    pads[sp[1]] = (paddings[1], paddings[1] + extra[1])
    pads = tuple(pads)
    any_padding = any(pads[a] != (0, 0) for a in sp)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides_full, pads)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides_full, pads)
        if attrs.get("exclusive", True) and any_padding:
            # divide by the count of valid (unpadded) elements per window —
            # covers both explicit padding and ceil_mode's implicit padding
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides_full, pads)
            out = summed / counts
        else:
            out = summed / (ksize[0] * ksize[1])
    return {"Out": [out]}


@register("adaptive_pool2d")
def _adaptive_pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    oh, ow = attrs["pooling_size"] if "pooling_size" in attrs else attrs["ksize"]
    n, c, h, w = x.shape
    assert h % oh == 0 and w % ow == 0, "adaptive pool needs divisible dims"
    x = x.reshape(n, c, oh, h // oh, ow, w // ow)
    if attrs.get("pooling_type", "avg") == "max":
        return {"Out": [jnp.max(x, axis=(3, 5))]}
    return {"Out": [jnp.mean(x, axis=(3, 5))]}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
@register("batch_norm", no_grad_inputs=("Mean", "Variance"))
def _batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    layout = attrs.get("data_layout", "NCHW")
    is_test = attrs.get("is_test", False) or attrs.get("use_global_stats", False) or ctx.is_test
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    red_axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]

    # statistics and normalization run in f32 even for bf16 activations
    # (the AMP trunk keeps x bf16 in HBM; the f32 upcast fuses into the
    # same loop, so the reduce accumulates at full precision for free) —
    # Y comes back in x's dtype, running stats/Saved* stay f32
    xs = x if x.dtype == jnp.float32 else x.astype(jnp.float32)
    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        mean_out, var_out = mean, var
    else:
        use_mean = jnp.mean(xs, axis=red_axes)
        use_var = jnp.var(xs, axis=red_axes)
        saved_mean, saved_var = use_mean, use_var
        mean_out = momentum * mean + (1 - momentum) * use_mean
        var_out = momentum * var + (1 - momentum) * use_var
        # running stats are pure state updates, not differentiated through
        mean_out = jax.lax.stop_gradient(mean_out)
        var_out = jax.lax.stop_gradient(var_out)

    inv = jax.lax.rsqrt(use_var + eps)
    y = (xs - use_mean.reshape(bshape)) * inv.reshape(bshape) * scale.reshape(
        bshape
    ) + bias.reshape(bshape)
    y = y.astype(x.dtype)
    return {
        "Y": [y],
        "MeanOut": [mean_out],
        "VarianceOut": [var_out],
        "SavedMean": [saved_mean],
        "SavedVariance": [jax.lax.stop_gradient(inv)],
    }


@register("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    begin = attrs.get("begin_norm_axis", 1)
    eps = attrs.get("epsilon", 1e-5)
    # statistics + normalization in f32 regardless of input dtype (bf16
    # inputs under AMP keep f32-quality stats; the upcast fuses into the
    # same loop), Y returned in the input dtype so the op is
    # dtype-transparent for the AMP trunk pass
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    y = (xf - mean) * inv
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(norm_shape).astype(jnp.float32)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(norm_shape).astype(jnp.float32)
    return {
        "Y": [y.astype(x.dtype)],
        "Mean": [jax.lax.stop_gradient(mean.reshape(mean.shape[:begin]))],
        "Variance": [jax.lax.stop_gradient(var.reshape(var.shape[:begin]))],
    }


@register("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """x * rsqrt(mean(x^2) + eps) * w over the last axis (w [d], or over
    X's last few axes: a gain a group).  The statistic
    and the normalisation run in f32 whatever X's dtype and Y leaves in
    X's dtype, so the op is dtype-transparent for the AMP trunk pass like
    layer_norm."""
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    y = xf * inv * ins["Scale"][0].astype(jnp.float32)
    return {"Y": [y.astype(x.dtype)]}


def _windows(x, taps, ahead=False):
    """The `taps` T-long windows of x [..., T, d] that a causal filter's
    taps read: window j is x_{t - (taps-1) + j}, or with `ahead` (the
    gradient's direction) x_{t + (taps-1) - j}; zeros beyond the ends.
    Slices of ONE padded copy of x as it is given: a fusion then reads x
    at `taps` offsets, where shifting a computed product makes the
    compiler write the product to memory first."""
    t = x.shape[-2]
    edge = [(0, taps - 1), (0, 0)] if ahead else [(taps - 1, 0), (0, 0)]
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 2) + edge)
    starts = [taps - 1 - j for j in range(taps)] if ahead else range(taps)
    return [xp[..., s:s + t, :] for s in starts]


def _f32(x):
    return x.astype(jnp.float32)


def _conv_taps(bcx, d, taps):
    """[(B u)_{t-(L-1)+j}]_j in f32, each from a window of bcx itself."""
    return [_f32(w[..., :d]) * _f32(w[..., 2 * d:])
            for w in _windows(bcx, taps)]


def _filtered(windows, k):
    """sum_j k[:, j] * windows[j]."""
    return sum(w * k[:, j] for j, w in enumerate(windows))


@jax.custom_vjp
def gated_short_conv(bcx, filt):
    """C * causal_depthwise_conv(B * u) over the T axis (second to last):
    bcx [..., T, 3d] holds B, C and u side by side, filt [d, L] one
    kernel a channel; v_t = sum_j filt[:, j] * (B u)_{t-(L-1)+j}, zeros
    left of t = 0.  L multiply-adds in f32, result in bcx's dtype: one
    pass over bcx."""
    d, taps = filt.shape
    v = _filtered(_conv_taps(bcx, d, taps), _f32(filt))
    return (_f32(bcx[..., d:2 * d]) * v).astype(bcx.dtype)


def _gsc_fwd(bcx, filt):
    return gated_short_conv(bcx, filt), (bcx, filt)


def _gsc_bwd(res, g):
    """Written out, not derived: autodiff shifts the [.., T, 3d] gradient
    once a tap through memory.  Here dv = g * C is read at L offsets
    ahead, as the forward reads B u behind; v is recomputed (cheaper than
    kept); one pass each over bcx and g for d bcx, one more for the
    filter's gradient."""
    bcx, filt = res
    d, taps = filt.shape
    k = _f32(filt)
    bu = _conv_taps(bcx, d, taps)
    v = _filtered(bu, k)
    d_bu = _filtered(
        [_f32(wg) * _f32(wx[..., d:2 * d]) for wg, wx in zip(
            _windows(g, taps, ahead=True), _windows(bcx, taps, ahead=True))],
        k)
    d_bcx = jnp.concatenate(
        [d_bu * _f32(bcx[..., 2 * d:]), _f32(g) * v,
         d_bu * _f32(bcx[..., :d])], -1).astype(bcx.dtype)
    dv = _f32(g) * _f32(bcx[..., d:2 * d])
    rows = tuple(range(bcx.ndim - 1))
    d_filt = jnp.stack([(dv * w).sum(rows) for w in bu], -1)
    return d_bcx, d_filt.astype(filt.dtype)


gated_short_conv.defvjp(_gsc_fwd, _gsc_bwd)


@register("short_conv")
def _short_conv(ctx, ins, attrs):
    """The gated short convolution between an operator's two projections
    (LFM2's conv layers): BCX [..., T, 3d] = h @ W_in, Filter [d, L], Out
    [..., T, d] = C * conv(B * u).  Memory-bound elementwise work: f32
    arithmetic whatever the dtype, Out in BCX's dtype (dtype-transparent
    for the AMP trunk pass like rms_norm); the gradient is
    gated_short_conv's own VJP."""
    with jax.named_scope("gate_conv"):
        out = gated_short_conv(ins["BCX"][0], ins["Filter"][0])
    return {"Out": [out]}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def causal_conv(x, filt, silu, bias=None):
    """Depthwise causal convolution over the T axis (second to last) of x
    [..., T, d], one filter a channel, filt [d, L]: c_t = sum_j filt[:, j]
    x_{t-(L-1)+j}, zeros left of t = 0, plus `bias` [d] where given
    (Mamba-2's); SiLU on the result where `silu`.
    The UNGATED form beside gated_short_conv (Kimi Linear's q, k and v):
    L multiply-adds in f32, result in x's dtype, one pass over x."""
    c = _filtered([_f32(w) for w in _windows(x, filt.shape[1])], _f32(filt))
    if bias is not None:
        c = c + _f32(bias)
    return (jax.nn.silu(c) if silu else c).astype(x.dtype)


def _cc_fwd(x, filt, silu, bias=None):
    return causal_conv(x, filt, silu, bias), (x, filt, bias)


def _cc_bwd(silu, res, g):
    """Written out as gated_short_conv's: c is made again from x (cheaper
    than kept), dc = g silu'(c) is read at L offsets ahead for dx as the
    forward reads x behind, and against x's windows for the filter; the
    bias's gradient is dc summed over the rows."""
    x, filt, bias = res
    taps, k = filt.shape[1], _f32(filt)
    xs = [_f32(w) for w in _windows(x, taps)]
    dc = _f32(g)
    if silu:
        c = _filtered(xs, k)
        if bias is not None:
            c = c + _f32(bias)
        sig = jax.nn.sigmoid(c)
        dc = dc * sig * (1.0 + c * (1.0 - sig))
    dx = _filtered(_windows(dc, taps, ahead=True), k)
    rows = tuple(range(x.ndim - 1))
    d_filt = jnp.stack([(dc * w).sum(rows) for w in xs], -1)
    return (dx.astype(x.dtype), d_filt.astype(filt.dtype),
            None if bias is None else dc.sum(rows).astype(bias.dtype))


causal_conv.defvjp(_cc_fwd, _cc_bwd)


@register("causal_conv")
def _causal_conv(ctx, ins, attrs):
    """X [..., T, d], Filter [d, L], optionally Bias [d] -> Out [..., T,
    d]: a depthwise causal convolution of L taps with no gate, `act`
    "silu" or none on its result.  f32 arithmetic whatever the dtype, Out in X's dtype
    (dtype-transparent for the AMP trunk pass like short_conv); the
    gradient is causal_conv's own VJP."""
    act = attrs.get("act") or ""
    if act not in ("", "silu"):
        raise ValueError("causal_conv act %r is neither silu nor none"
                         % (act,))
    bias = ins["Bias"][0] if ins.get("Bias") else None
    return {"Out": [causal_conv(ins["X"][0], ins["Filter"][0],
                                act == "silu", bias)]}


@register("group_norm")
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs.get("groups", 32)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape(n, g, c // g, *x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) * jax.lax.rsqrt(var + eps)).reshape(x.shape)
    shp = [1, c] + [1] * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(shp)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(shp)
    return {"Y": [y], "Mean": [mean.reshape(n, g)], "Variance": [var.reshape(n, g)]}


@register("instance_norm")
def _instance_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    y = (x - mean) * jax.lax.rsqrt(var + eps)
    shp = [1, x.shape[1]] + [1] * (x.ndim - 2)
    if ins.get("Scale"):
        y = y * ins["Scale"][0].reshape(shp)
    if ins.get("Bias"):
        y = y + ins["Bias"][0].reshape(shp)
    return {"Y": [y]}


@register("l2_normalize")
def _l2_normalize(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-12)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


@register("lrn")
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k, alpha, beta = attrs.get("k", 2.0), attrs.get("alpha", 1e-4), attrs.get("beta", 0.75)
    sq = jnp.square(x)
    pad = n // 2
    sq_pad = jnp.pad(sq, ((0, 0), (pad, pad), (0, 0), (0, 0)))
    acc = jnp.zeros_like(x)
    for i in range(n):
        acc = acc + sq_pad[:, i : i + x.shape[1]]
    mid = jnp.power(k + alpha * acc, beta)
    return {"Out": [x / mid], "MidOut": [mid]}


# ---------------------------------------------------------------------------
# dropout (operators/dropout_op.*)
# ---------------------------------------------------------------------------
@register("dropout", needs_rng=True)
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    is_test = attrs.get("is_test", False) or ctx.is_test
    if is_test:
        if impl == "upscale_in_train":
            return {"Out": [x], "Mask": [jnp.ones_like(x)]}
        return {"Out": [x * (1.0 - p)], "Mask": [jnp.ones_like(x)]}
    mb = current_microbatch_rows()
    if mb is not None and x.ndim >= 1:
        # pipeline microbatch: draw the mask over the FULL global batch
        # rows (bit-identical to the unpipelined trace: the same key and
        # shape give the same bits) and slice this microbatch's window
        total_rows, row_offset = mb
        keep = jax.random.bernoulli(
            ctx.rng(attrs), 1.0 - p, (total_rows,) + tuple(x.shape[1:])
        )
        keep = jax.lax.dynamic_slice_in_dim(keep, row_offset, x.shape[0], 0)
    else:
        keep = jax.random.bernoulli(ctx.rng(attrs), 1.0 - p, x.shape)
    mask = keep.astype(x.dtype)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / (1.0 - p), 0.0)
    else:
        out = x * mask
    return {"Out": [out], "Mask": [mask]}


# ---------------------------------------------------------------------------
# recurrent blocks: lstm / gru as scan ops
# ---------------------------------------------------------------------------
def _lstm_cell(c_prev, h_prev, gates, forget_bias=0.0):
    i, f, c_hat, o = jnp.split(gates, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f + forget_bias)
    o = jax.nn.sigmoid(o)
    c = f * c_prev + i * jnp.tanh(c_hat)
    h = o * jnp.tanh(c)
    return c, h


@register("lstm_unit")
def _lstm_unit(ctx, ins, attrs):
    gates, c_prev = ins["X"][0], ins["C_prev"][0]
    c, h = _lstm_cell(c_prev, None, gates, attrs.get("forget_bias", 0.0))
    return {"C": [c], "H": [h]}


def _mm_act(z, act):
    """fc's epilogue activation (exact erf gelu / beta-1 swish: the same
    defaults as the op lowerings in math_ops.ACTIVATIONS)."""
    if act in ("", "identity"):
        return z
    if act == "relu":
        return jnp.maximum(z, 0.0)
    if act == "tanh":
        return jnp.tanh(z)
    if act == "sigmoid":
        return jax.nn.sigmoid(z)
    if act == "gelu":
        return jax.nn.gelu(z, approximate=False)
    if act == "swish":
        return z * jax.nn.sigmoid(z)
    raise ValueError("matmul epilogue: unsupported activation %r" % (act,))


# The activations whose derivative reads the pre-activation itself (relu's,
# tanh's and sigmoid's are read off the op's output, an identity's needs
# nothing): the step needs both the pre-activation, for the backward, and the
# output, for the next op.  Applied behind the reshape to [..., N] the
# compiler keeps the pre-activation alone and computes the activation again
# inside every matmul that consumes it; applied to the [M, N] product it
# fuses bias and activation into the matmul that made it and writes both
# (GPT-2 345M on a v5e: PERF.md section 6, PR 47).
FC_PRODUCT_EPILOGUE_ACTS = ("gelu", "swish")


@register("fc")
def _fc(ctx, ins, attrs):
    """Fused fully-connected (fc_op of fc_fuse_pass.cc): mul + bias-add +
    activation in one op: one MXU matmul with an XLA-fused epilogue, on
    the [M, N] product under FC_PRODUCT_EPILOGUE_ACTS (the same values: the
    reshape moves behind two elementwise ops)."""
    x, w = ins["Input"][0], ins["W"][0]
    k = int(attrs.get("in_num_col_dims", 1))
    x2 = x.reshape((int(np.prod(x.shape[:k])), -1))
    act = attrs.get("activation_type", "") or ""
    bias = ins["Bias"][0].reshape(-1) if ins.get("Bias") else None
    out = x2 @ w
    shape = tuple(x.shape[:k]) + (w.shape[-1],)
    if act in FC_PRODUCT_EPILOGUE_ACTS:
        if bias is not None:
            out = out + bias.reshape(1, -1)
        return {"Out": [_mm_act(out, act).reshape(shape)]}
    out = out.reshape(shape)
    if bias is not None:
        out = out + bias.reshape((1,) * k + (-1,))
    return {"Out": [_mm_act(out, act)]}


def _swiglu_dense(x2d, wg, wu):
    g = jnp.dot(x2d, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x2d, wu, preferred_element_type=jnp.float32)
    return (g * jax.nn.sigmoid(g) * u).astype(x2d.dtype)


@register("fused_swiglu")
def _fused_swiglu(ctx, ins, attrs):
    """Fused SwiGLU gating (swiglu_fuse_pass target): silu(x @ GateW) *
    (x @ UpW) in one op, both projections accumulated in f32."""
    x, wg, wu = ins["X"][0], ins["GateW"][0], ins["UpW"][0]
    k = int(attrs.get("x_num_col_dims", 1))
    x2 = x.reshape((int(np.prod(x.shape[:k])), -1))
    out = _swiglu_dense(x2, wg, wu)
    return {"Out": [out.reshape(tuple(x.shape[:k]) + (wg.shape[-1],))]}


def _add_ln_dense(x2d, y2d, gamma, beta, eps):
    s = x2d.astype(jnp.float32) + y2d.astype(jnp.float32)
    mean = jnp.mean(s, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(s - mean), axis=-1, keepdims=True)
    yn = (s - mean) * jax.lax.rsqrt(var + eps)
    return (s.astype(x2d.dtype),
            (yn * gamma + beta).astype(x2d.dtype))


@register("fused_residual_ln")
def _fused_residual_ln(ctx, ins, attrs):
    """Residual add + layer norm (residual_ln_fuse_pass target): BOTH the
    sum (the residual stream downstream consumers keep reading under its
    original name) and the normalized output come out of one op.  Stats
    in f32 like layer_norm."""
    x, y = ins["X"][0], ins["Y"][0]
    eps = attrs.get("epsilon", 1e-5)
    h = x.shape[-1]
    s2, o2 = _add_ln_dense(
        x.reshape(-1, h), y.reshape(-1, h), ins["Scale"][0].reshape(h),
        ins["Bias"][0].reshape(h), eps)
    s = s2.reshape(x.shape)
    sf = s.astype(jnp.float32)
    mean = jnp.mean(sf, axis=-1)
    var = jnp.var(sf, axis=-1)
    return {
        "Sum": [s],
        "Y": [o2.reshape(x.shape)],
        "Mean": [jax.lax.stop_gradient(mean)],
        "Variance": [jax.lax.stop_gradient(var)],
    }


@register("fusion_seqconv_eltadd_relu")
def _fusion_seqconv_eltadd_relu(ctx, ins, attrs):
    """fused/fusion_seqconv_eltadd_relu_op.cc: sequence_conv + bias + relu
    as one op (seqconv_eltadd_relu_fuse_pass target)."""
    from ..core.registry import get_op

    conv_ins = {"X": ins["X"], "Filter": ins["Filter"]}
    if ins.get("SeqLen"):
        conv_ins["SeqLen"] = ins["SeqLen"]
    out = get_op("sequence_conv").lower(ctx, conv_ins, attrs)["Out"][0]
    out = out + ins["Bias"][0].reshape((1,) * (out.ndim - 1) + (-1,))
    return {"Out": [jnp.maximum(out, 0)]}


@register("fusion_seqexpand_concat_fc")
def _fusion_seqexpand_concat_fc(ctx, ins, attrs):
    """fused/fusion_seqexpand_concat_fc_op.cc: X[0] is a [B, T, D0]
    sequence; every further X[i] is a per-example [B, Di] vector expanded
    to all T steps; features concat and feed one fc (+ activation)."""
    xs = ins["X"]
    seq = xs[0]
    b, t = seq.shape[0], seq.shape[1]
    parts = [seq] + [
        jnp.broadcast_to(v[:, None, :], (b, t, v.shape[-1])) for v in xs[1:]
    ]
    cat = jnp.concatenate(parts, axis=-1)
    out = cat @ ins["FCWeight"][0]
    if ins.get("FCBias"):
        out = out + ins["FCBias"][0].reshape(1, 1, -1)
    act = attrs.get("fc_activation", "identity")
    fn = {"identity": lambda x: x, "relu": jax.nn.relu, "tanh": jnp.tanh,
          "sigmoid": jax.nn.sigmoid}[act]
    return {"Out": [fn(out)]}


@register("fused_embedding_fc_lstm", no_grad_inputs=("Ids",))
def _fused_embedding_fc_lstm(ctx, ins, attrs):
    """fused/fused_embedding_fc_lstm_op.cc capability: embedding lookup +
    input projection + LSTM recurrence as one op
    (embedding_fc_lstm_fuse_pass target).  Inputs: Ids, Embeddings
    [vocab, D], WeightX [D, 4H], WeightH [H, 4H], optional BiasX/Bias,
    optional SeqLen/H0/C0; same outputs as `lstm`."""
    from ..core.registry import get_op

    ids = ins["Ids"][0].astype(jnp.int32)
    if ids.ndim >= 2 and ids.shape[-1] == 1:
        ids = ids[..., 0]
    from .compat_ops import project_input_maybe

    emb = jnp.take(ins["Embeddings"][0], ids, axis=0)  # [B, T, D]
    xproj = project_input_maybe(dict(ins, Input=[emb]))["Input"][0]
    lstm_ins = {"Input": [xproj], "Weight": ins["WeightH"]}
    for slot in ("Bias", "SeqLen", "H0", "C0"):
        if ins.get(slot):
            lstm_ins[slot] = ins[slot]
    out = get_op("padded_lstm").lower(ctx, lstm_ins, attrs)
    return {
        "Hidden": out["Hidden"],
        "Cell": out["CellSeq"],
        "LastH": out["LastH"],
        "LastC": out["LastC"],
    }


def _seq_lens(ins, bsz, t):
    """[bsz] int32 row lengths: the SeqLen input, else every row is t."""
    if not ins.get("SeqLen"):
        return jnp.full((bsz,), t, jnp.int32)
    return ins["SeqLen"][0].reshape(-1).astype(jnp.int32)


def _lstm_seq_dense(xproj, w, h0, c0, lens, reverse=False):
    """Masked LSTM recurrence over [B, T, 4H] projected gates: one
    lax.scan over time (from the last step when `reverse`); a row holds
    its state at steps >= its length, so the final carry IS its last
    valid h/c.  Returns (hidden_seq, cell_seq, last_h, last_c)."""

    def step(carry, inp):
        h, c = carry
        xt, t = inp
        c_new, h_new = _lstm_cell(c, h, xt + h @ w)
        act = (t < lens)[:, None].astype(h.dtype)
        c_new = act * c_new + (1 - act) * c
        h_new = act * h_new + (1 - act) * h
        return (h_new, c_new), (h_new, c_new)

    xs = jnp.swapaxes(xproj, 0, 1)
    ts = jnp.arange(xproj.shape[1])
    (h_fin, c_fin), (hs, cs) = jax.lax.scan(step, (h0, c0), (xs, ts),
                                            reverse=reverse)
    return jnp.swapaxes(hs, 0, 1), jnp.swapaxes(cs, 0, 1), h_fin, c_fin


@register("padded_lstm")
def _padded_lstm(ctx, ins, attrs):
    """TPU-native LSTM over padded [batch, time, 4*hidden] projected input.

    Replaces the reference's LoD-reordered `lstm_op` (sequence2batch +
    per-step gemm): here the input projection is done outside as one big
    matmul and the recurrence is a lax.scan over time with a length mask.
    Inputs: Input (projected gates), Weight [hidden, 4*hidden], Bias
    [4*hidden], optional SeqLen [batch], optional H0/C0.
    """
    xproj = ins["Input"][0]  # [B, T, 4H]
    w = ins["Weight"][0]  # [H, 4H]
    bsz, t, h4 = xproj.shape
    hid = h4 // 4
    h0 = ins["H0"][0] if ins.get("H0") else jnp.zeros((bsz, hid), xproj.dtype)
    c0 = ins["C0"][0] if ins.get("C0") else jnp.zeros((bsz, hid), xproj.dtype)
    if ins.get("Bias"):  # folds into the projected gates
        xproj = xproj + ins["Bias"][0].reshape(1, 1, -1)
    lens = _seq_lens(ins, bsz, t)
    hs, cs, h_fin, c_fin = _lstm_seq_dense(
        xproj, w, h0, c0, lens, attrs.get("is_reverse", False))
    return {"Hidden": [hs], "CellSeq": [cs], "LastH": [h_fin],
            "LastC": [c_fin]}


def _gru_seq_dense(xproj, w, h0, lens, reverse=False):
    """Masked GRU recurrence over [B, T, 3H] projected input, gate layout
    [update|reset|candidate], blend h = u*c + (1-u)*h_prev
    (math/detail/gru_kernel.h:58-63: out = prev - u*prev + u*state): one
    lax.scan over time (from the last step when `reverse`); a row holds h
    at steps >= its length, so the final carry IS its last valid hidden
    state (a length-0 row yields h0).  Returns (hidden_seq, last_h)."""
    hid = xproj.shape[-1] // 3
    w_uz, w_c = w[:, : 2 * hid], w[:, 2 * hid:]

    def step(h, inp):
        xt, t = inp
        gates = xt[:, : 2 * hid] + h @ w_uz
        u = jax.nn.sigmoid(gates[:, :hid])
        r = jax.nn.sigmoid(gates[:, hid:])
        c = jnp.tanh(xt[:, 2 * hid:] + (r * h) @ w_c)
        h_new = u * c + (1.0 - u) * h
        act = (t < lens)[:, None].astype(h.dtype)
        h_new = act * h_new + (1 - act) * h
        return h_new, h_new

    xs = jnp.swapaxes(xproj, 0, 1)
    ts = jnp.arange(xproj.shape[1])
    h_fin, hs = jax.lax.scan(step, h0, (xs, ts), reverse=reverse)
    return jnp.swapaxes(hs, 0, 1), h_fin


@register("padded_gru")
def _padded_gru(ctx, ins, attrs):
    """GRU over padded [batch, time, 3*hidden] projected input (gru_op analog)."""
    xproj = ins["Input"][0]
    w = ins["Weight"][0]  # [H, 3H] -> [update|reset, candidate]
    bsz, t, h3 = xproj.shape
    h0 = (ins["H0"][0] if ins.get("H0")
          else jnp.zeros((bsz, h3 // 3), xproj.dtype))
    lens = _seq_lens(ins, bsz, t)
    hs, h_fin = _gru_seq_dense(xproj, w, h0, lens,
                               attrs.get("is_reverse", False))
    return {"Hidden": [hs], "LastH": [h_fin]}


# ---------------------------------------------------------------------------
# misc nn
# ---------------------------------------------------------------------------
@register("im2sequence")
def _im2sequence(ctx, ins, attrs):
    """Extract conv-style patches into a sequence (im2sequence_op.cc):
    x [N, C, H, W] -> [N, OH*OW, C*kh*kw] (padded layout; the reference
    emits LoD rows N*OH*OW x C*kh*kw)."""
    x = ins["X"][0]
    kh, kw = attrs["kernels"]
    sh, sw = attrs.get("strides", [1, 1])
    pads = attrs.get("paddings", [0, 0, 0, 0])  # up, left, down, right
    n, c, h, w = x.shape
    patches = jax.lax.conv_general_dilated_patches(
        x,
        filter_shape=(kh, kw),
        window_strides=(sh, sw),
        padding=((pads[0], pads[2]), (pads[1], pads[3])),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # [N, C*kh*kw, OH, OW]
    ckk = patches.shape[1]
    out = jnp.transpose(patches.reshape(n, ckk, -1), (0, 2, 1))
    return {"Out": [out]}


@register("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh, ow = attrs.get("out_h"), attrs.get("out_w")
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="bilinear")
    return {"Out": [out]}


@register("nearest_interp")
def _nearest_interp(ctx, ins, attrs):
    x = ins["X"][0]
    oh, ow = attrs.get("out_h"), attrs.get("out_w")
    out = jax.image.resize(x, (x.shape[0], x.shape[1], oh, ow), method="nearest")
    return {"Out": [out]}


@register("maxout")
def _maxout(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs["groups"]
    n, c, h, w = x.shape
    return {"Out": [jnp.max(x.reshape(n, c // g, g, h, w), axis=2)]}


@register("pixel_shuffle")
def _pixel_shuffle(ctx, ins, attrs):
    x = ins["X"][0]
    r = attrs.get("upscale_factor", 2)
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return {"Out": [x.reshape(n, c // (r * r), h * r, w * r)]}


def _qvec_attention_mesh(q, k, v, qstart, scale, mesh, axis):
    """The vector-QStart attention lowered MESH-CLEAN over `axis`
    (heads): a 4-D dense einsum bracketed by sharding constraints, so the
    SPMD partitioner keeps the KV pool's heads-axis placement instead of
    re-laying it out (per-row causal cutoffs are head-independent).
    q/k/v: rank-4 [B, H, Tq|Tk, D]; qstart: [B]."""
    from .pallas_kernels import NEG_INF
    from jax.sharding import NamedSharding, PartitionSpec as P

    b, h, t, d = q.shape
    tk = k.shape[2]
    sh = NamedSharding(mesh, P(None, axis, None, None))
    qc = jax.lax.with_sharding_constraint(q, sh)
    kc = jax.lax.with_sharding_constraint(k, sh)
    vc = jax.lax.with_sharding_constraint(v, sh)
    s = (jnp.einsum("bhqd,bhkd->bhqk", qc, kc).astype(jnp.float32)
         * scale)  # [B, H, Tq, Tk]
    q_pos = (qstart.reshape(b, 1).astype(jnp.int32)
             + jnp.arange(t, dtype=jnp.int32)[None, :])  # [B, Tq]
    keep = (q_pos[:, None, :, None]
            >= jnp.arange(tk, dtype=jnp.int32)[None, None, None, :])
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p.astype(qc.dtype), vc)
    return jax.lax.with_sharding_constraint(out, sh)


def _qstart_attention(q, k, v, qstart, scale, window):
    """fused_attention's decode paths, dense XLA: causal cutoffs in GLOBAL
    positions from QStart (a scalar: chunked decode; [B]: the ragged
    serving step).  q/k/v: [B, H, Tq|Tk, D]."""
    from .pallas_kernels import NEG_INF, _dense_attention

    b, h, t, d = q.shape
    tk = k.shape[2]
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    if qstart.ndim == 0:
        out = _dense_attention(qf, kf, vf, True, float(scale),
                               window=window, qoff=qstart)
        return out.reshape(b, h, t, d)
    # PER-ROW offset-causal (the continuous-batching ragged step):
    # QStart is [B], row b's query i sits at global position
    # QStart[b] + i — every slot in the serving pool gets its own
    # causal cutoff inside ONE dispatch.  Row math is row-independent,
    # so the serving bit-exactness contract — a slot equals its solo
    # run through the same program — holds.
    if int(qstart.shape[0]) != b:
        raise ValueError(
            "fused_attention: vector QStart must be [batch]=%d, got %s"
            % (b, tuple(qstart.shape)))
    if window:
        raise ValueError(
            "fused_attention: window is not supported with per-row "
            "QStart")
    # GSPMD serving mesh (executor._run_spmd binds the context): heads
    # are embarrassingly parallel under per-row qstart, so the mesh-clean
    # form shards the HEADS axis of a 4-D einsum (the flattened [B*H]
    # layout would interleave shards across batch rows).  Row math is
    # untouched: pooled == solo rides through sharding.
    from ..parallel.partition_rules import current_spmd

    spmd = current_spmd()
    if spmd is not None:
        from ..parallel.mesh import mesh_axis_sizes

        mesh, rules = spmd
        axis = rules.mp_axis
        nsh = mesh_axis_sizes(mesh).get(axis, 1)
        if nsh > 1 and h % nsh == 0:
            return _qvec_attention_mesh(q, k, v, qstart, float(scale),
                                        mesh, axis)
    s = (jnp.einsum("bqd,bkd->bqk", qf, kf).astype(jnp.float32)
         * float(scale))  # [B*H, Tq, Tk]
    q_pos = (qstart.reshape(b, 1).astype(jnp.int32)
             + jnp.arange(t, dtype=jnp.int32)[None, :])  # [B, Tq]
    keep = q_pos[:, :, None] >= jnp.arange(tk, dtype=jnp.int32)[
        None, None, :]  # [B, Tq, Tk]
    keep = jnp.broadcast_to(keep[:, None], (b, h, t, tk)).reshape(
        b * h, t, tk)
    s = jnp.where(keep, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bqk,bkd->bqd", p.astype(qf.dtype), vf)
    return out.reshape(b, h, t, d)


@register("fused_attention", no_grad_inputs=("QStart",))
def _fused_attention(ctx, ins, attrs):
    """Fused scaled-dot-product attention (the cuDNN-fused-kernel slot of
    the reference, TPU-style).  Q/K/V: [batch, heads, T, d], or under
    layout "bthd" [batch, T, heads, d] with Out alike (what the
    projections write, reshaped: attention_layout_fuse_pass).  Training
    path (no QStart): the blockwise kernel where platform and shape say
    so (_flash_engages), its one-tile form under that kernel's lengths
    (_short_engages; read in place where the layout is "bthd" and
    _in_place_engages), dense XLA otherwise.  QStart paths (chunked and
    ragged decode): dense XLA (_qstart_attention).  Every path but the
    in-place one runs on [batch, heads, T, d]: a "bthd" op transposes
    to it here and back, so the op means one thing in both layouts."""
    from .pallas_kernels import _dense_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    causal = bool(attrs.get("causal", False))
    layout = attention_layout(attrs)
    if layout == "bthd":
        if _in_place_engages(ctx, ins, attrs):
            return {"Out": [_in_place_attention(ins, causal,
                                                attrs.get("scale"))]}
        heads_first = dict(ins, **{slot: [jnp.transpose(ins[slot][0],
                                                        (0, 2, 1, 3))]
                                   for slot in ("Q", "K", "V")})
        out = _fused_attention(ctx, heads_first,
                               dict(attrs, layout="bhtd"))["Out"][0]
        return {"Out": [jnp.transpose(out, (0, 2, 1, 3))]}
    window = int(attrs.get("window", 0) or 0)  # sliding-window (causal)
    if window < 0:
        raise ValueError("fused_attention: window must be >= 0")
    if window and not causal:
        raise ValueError(
            "fused_attention: window requires causal=True (consistent "
            "across the pallas and dense paths)")
    scale = attrs.get("scale") or 1.0 / (q.shape[-1] ** 0.5)
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]
    # chunked-decode global query offset: query i at position QStart+i,
    # keys at their cache indices — Tq may differ from Tk.  A size-1
    # QStart is the classic scalar offset (one chunk position for the
    # whole batch); size B keeps PER-ROW offsets (ragged serving step).
    qstart = None
    if ins.get("QStart"):
        qstart = ins["QStart"][0].reshape(-1)
        qstart = qstart.reshape(()) if qstart.shape[0] == 1 else qstart
    if qstart is not None:
        if not causal:
            raise ValueError("fused_attention: QStart requires causal=True")
        if ins.get("Bias") or ins.get("SegmentIds"):
            raise ValueError(
                "fused_attention: QStart owns the causal cutoffs — "
                "Bias/SegmentIds are not combinable with it")
        if dv != d:
            raise ValueError(
                "fused_attention: the QStart (cached decode) paths take V "
                "at Q's width, got %d against %d" % (dv, d))
        return {"Out": [_qstart_attention(q, k, v, qstart, scale, window)]}
    if causal and t != tk:
        raise ValueError(
            "fused_attention: causal requires Tq == Tk, got %d vs %d" % (t, tk)
        )
    qf = q.reshape(b * h, t, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, dv)
    kbias = kbias_b = seg_b = None
    if ins.get("Bias"):
        # additive key-padding bias, rank-1 in the key axis: [B, Tk] (or any
        # shape squeezing to it, e.g. the reference-style [B, 1, 1, Tk]);
        # broadcast over heads and query rows without ever materializing
        # the [Tq, Tk] score matrix
        kbias_b = ins["Bias"][0].reshape(b, tk).astype(jnp.float32)
        kbias = jnp.broadcast_to(
            kbias_b[:, None, :], (b, h, tk)).reshape(b * h, tk)
    seg = None
    if ins.get("SegmentIds"):
        # sequence packing (reader.packing): [B, T] int ids; query i sees
        # key j iff the ids match.  Rides the flash kernels as two more
        # rank-1 [BH, T] operands (compared per score tile), dense
        # otherwise.
        if t != tk:
            raise ValueError(
                "fused_attention: SegmentIds requires Tq == Tk "
                "(self-attention over one packed row)")
        seg_b = ins["SegmentIds"][0].reshape(b, t).astype(jnp.int32)
        seg = jnp.broadcast_to(
            seg_b[:, None, :], (b, h, t)).reshape(b * h, t)
    # The training path (no QStart).  One algorithm, engaged by what the
    # lowering can see: the blockwise kernel where _flash_engages says the
    # step is placed on a TPU and the shape is one the chip sweep found
    # it ahead at, its one-tile form at the short lengths of
    # _short_engages, dense XLA everywhere else.  No flag is read here.
    if _short_engages(ctx, t, tk, d, dv, window, seg is not None):
        from .kernel_tuning import note_kernel
        from .pallas_kernels import short_attention

        note_kernel("attention")
        note_kernel("attention_short")
        out = short_attention(qf, kf, vf, kbias, causal, float(scale))
    elif _flash_engages(ctx, t, tk, d, dv):
        from .kernel_tuning import (note_band_grid, note_kernel,
                                    note_tile_classes)
        from .pallas_kernels import (band_grid_steps, flash_attention,
                                     tile_class_stats)
        from .spmd_epilogue import mesh_ctx, spmd_flash_attention

        note_kernel("attention")
        # which widths the kernel engaged with, where they are not one
        # width of at most 128 (latent attention's 192 over 128, 256)
        if dv != d or d > 128:
            note_kernel("attention_qk%d_v%d" % (d, dv))
        mc, blk = mesh_ctx(), _flash_block(t, window)
        if window:  # the grid a head's forward walks against its band
            note_band_grid(t, window, blk, blk,
                           *band_grid_steps(t, blk, blk, window))
        if causal:  # the pairs its bodies compute against the visible
            note_tile_classes(t, window, blk, blk, d,
                              tile_class_stats(t, d, blk, blk, window))
        if mc is None:
            out = flash_attention(qf, kf, vf, kbias, causal, float(scale),
                                  blk, blk, window, seg)
        else:  # rows over dp, heads over mp: the sharding is the op's
            out = spmd_flash_attention(
                mc, q, k, v, kbias_b, seg_b, causal, float(scale), blk, blk,
                window).reshape(b * h, t, dv)
    else:
        out = _dense_attention(qf, kf, vf, causal, float(scale), kbias,
                               window=window, seg=seg)
    return {"Out": [out.reshape(b, h, t, dv)]}


# When fused_attention's training path takes the blockwise kernel
# (pallas_kernels.flash_attention), and with which blocks: constants from
# one sweep on a v5e over the transformer cells' attention shapes
# (tools/attention_sweep.py; the table is in CHANGES.md, PR 29), not a
# tuning-cache entry consulted at trace time.  Forward + backward alone,
# bf16, kernel against dense: T = 256 3.64 against 2.85 ms (not this
# kernel's: its one-tile form takes the lengths under 512, _short_engages),
# T = 512 0.77 against 1.11, T = 1024 0.95 against 2.66, T = 4096 4.0
# against 29.5; at every length the largest square block was fastest: for
# the triangle, and for a band of two blocks or more (PR 41's sweep).
# A NARROWER band takes the block its window says (_flash_block), from
# tools/attention_sweep.py --band-blocks on a v5e (CHANGES.md, PR 66):
# Laguna-XS.2's window core, 64 heads x 6144 x 128, ms forward + backward
# in blocks of 1024 / 512 / 256 / 128 by window:
#   window  128:  8.98 /  6.73 /  7.45 /  8.53
#   window  256:  8.97 /  6.73 /  7.60 / 12.29
#   window  512:  8.98 /  6.58 / 10.70 / 19.47
#   window 1024:  6.96 /  9.14 / 16.54 / 33.18
#   window 2048: 10.06 / 13.60 / 26.86 / 58.09
# A block the window is a multiple of puts the band's edge on a block
# boundary (no tile cut by the diagonal AND the edge, both cuts in strips),
# and under 512 a block costs more a step than its masked pairs save: 512
# is ahead at windows 128 and 256 with every tile whole and masked.
_FLASH_MIN_T = 512
_FLASH_BLOCKS = (1024, 512, 256, 128)
_FLASH_BAND_MIN_BLOCK = 512


def _flash_block(t, window=0):
    """The largest block that divides t (both sides of a tile take it);
    t itself, one full-length block, for a t no block divides.  Under a
    `window` narrower than t that a block divides, the largest block that
    divides the window too or is no larger than _FLASH_BAND_MIN_BLOCK: the
    band's edge on a block boundary, in blocks not under that floor."""
    fits = [b for b in _FLASH_BLOCKS if t % b == 0]
    if 0 < window < t and any(window % b == 0 for b in fits):
        fits = [b for b in fits
                if window % b == 0 or b <= _FLASH_BAND_MIN_BLOCK]
    return fits[0] if fits else t


# (width of Q and K, width of V) the kernel takes: one head width, 64 or
# 128, or latent attention's 192-wide scores (128 without position + 64
# rotary) over 128-wide values, the score tile ONE 192-wide contraction
# (tools/mla_kernel_sweep.py on a v5e; the table is in CHANGES.md, PR 37).
_FLASH_WIDTHS = ((64, 64), (128, 128), (192, 128), (256, 256))


def _placed_on_tpu(ctx):
    """The platform is the placed device's (LowerCtx.platform, which the
    Executor states), the process's default backend only where a caller
    did not say."""
    return (getattr(ctx, "platform", None) or jax.default_backend()) == "tpu"


def _flash_engages(ctx, tq, tk, d, dv=None):
    """Self-attention on a TPU-placed step, T a multiple of 128 at or
    above _FLASH_MIN_T, (Q/K width, V width) one of _FLASH_WIDTHS: 64 or
    128 for both, or 192 over 128 (V's width is Q's where a caller gives
    none)."""
    return (_placed_on_tpu(ctx) and tq == tk and tq % 128 == 0
            and tq >= _FLASH_MIN_T
            and (d, d if dv is None else dv) in _FLASH_WIDTHS)


# Under _FLASH_MIN_T the whole sequence is one tile and a grid step holds
# several heads (pallas_kernels.short_attention).  It engages at the (T, head
# width) pairs one sweep on a v5e ran it at and found it ahead of both the
# dense lowering and the blockwise kernel, and at no other: the sweep's table
# has a row for each (tools/attention_sweep.py --short; CHANGES.md, PR 62).
# Forward + backward alone, bf16, one-tile against dense against the
# blockwise kernel at one block of T: (64, 64) 1.92 / 3.17 / 5.89 ms,
# (128, 64) 0.56 / 1.30 / 3.84, (256, 64) 1.06 / 2.85 / 3.54, (384, 64)
# 1.11 / 4.04 / 3.22, (256, 128) 1.21 / 1.31 / 1.26.  128-wide heads at the
# other lengths are NOT its: (64, 128) 1.10 / 0.62 / 2.23 and (128, 128)
# 1.10 / 0.62 / 1.42 (dense ahead: its 128-lane arrays have no padding to
# copy), (384, 128) 1.30 / 2.20 / 1.14.  The sequence is the kernels' lane
# dim: whole 128-lane tiles, or 64, two heads side by side.  Those rows
# start from q, k, v stored [B H, T, d]; from the projections' [B, T, H d]
# (64, 64) reads 2.16 and (256, 64) 1.69 with the copies counted, and a
# Program that asks for layout "bthd" takes the kernel in place there
# (_IN_PLACE_SHAPES below: 0.53 and 0.87).
_SHORT_SHAPES = ((64, 64), (128, 64), (256, 64), (384, 64), (256, 128))


def _short_engages(ctx, tq, tk, d, dv=None, window=0, seg=False):
    """Self-attention on a TPU-placed step at one of _SHORT_SHAPES' (T, head
    width) pairs, V as wide as Q, no window, no segment ids, no live mesh
    (spmd_flash_attention is the blockwise kernel's; no cell shards such a
    shape)."""
    from .spmd_epilogue import mesh_ctx

    return (_placed_on_tpu(ctx) and tq == tk and dv in (None, d)
            and (tq, d) in _SHORT_SHAPES and not window and not seg
            and mesh_ctx() is None)


ATTENTION_LAYOUTS = ("bhtd", "bthd")


def attention_layout(attrs):
    """fused_attention's `layout`: "bhtd" (Q/K/V [B, H, T, d], the default
    and every program's before PR 63) or "bthd" ([B, T, H, d])."""
    layout = attrs.get("layout") or "bhtd"
    if layout not in ATTENTION_LAYOUTS:
        raise ValueError("fused_attention: layout is one of %s, got %r"
                         % (ATTENTION_LAYOUTS, layout))
    return layout


# Where a "bthd" op takes the one-tile kernel IN PLACE (pallas_kernels.
# short_attention(..., heads=H): blocks of the projections' [B, T, H d], no
# copy of an operand): the (T, head width) pairs whose row of the chip sweep
# from operands stored [B, T, H d] has it ahead (tools/attention_sweep.py
# --short --in-place; the table is in CHANGES.md, PR 63).  Forward + backward
# alone on a v5e, bf16, causal, key bias, eight heads of 64, in place
# against the one-tile kernel above behind the copies XLA needs to reach its
# layout against dense: (64, 64) at B 512 0.53 / 2.16 / 3.14 ms (two heads a
# product; a 64-lane slice a head reads 1.75 where the pair reads 1.12, both
# one sequence a loop body), (256, 64) at B 128 0.87 / 1.69 / 3.05.  A _SHORT_SHAPES pair that has no such row transposes in the
# lowering and takes the kernel above, as before the layout existed.
_IN_PLACE_SHAPES = ((64, 64), (256, 64))


def _in_place_engages(ctx, ins, attrs):
    """A "bthd" op whose [B, T, H, d] operands _short_engages would take,
    at one of _IN_PLACE_SHAPES, no QStart."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    return (not ins.get("QStart") and q.shape[2] == k.shape[2]
            and (q.shape[1], q.shape[3]) in _IN_PLACE_SHAPES
            and _short_engages(ctx, q.shape[1], k.shape[1], q.shape[3],
                               v.shape[3], int(attrs.get("window", 0) or 0),
                               bool(ins.get("SegmentIds"))))


def _in_place_attention(ins, causal, scale):
    from .kernel_tuning import note_kernel
    from .pallas_kernels import short_attention

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    b, t, h, d = q.shape
    kbias = None
    if ins.get("Bias"):  # [B, Tk] (or any shape squeezing to it), f32
        kbias = ins["Bias"][0].reshape(b, t).astype(jnp.float32)
    note_kernel("attention")
    note_kernel("attention_short")
    note_kernel("attention_short_in_place")
    out = short_attention(
        q.reshape(b, t, h * d), k.reshape(b, t, h * d),
        v.reshape(b, t, h * v.shape[3]), kbias, causal,
        float(scale or 1.0 / (d ** 0.5)), h)
    return out.reshape(b, t, h, v.shape[3])


@register("sequence_conv")
def _sequence_conv(ctx, ins, attrs):
    """Context-window convolution over padded sequences
    (sequence_ops/sequence_conv_op.cc): for each timestep concatenate
    context_length steps starting at context_start, matmul with Filter
    [ctx_len * D, out].  Positions outside the sequence contribute zeros
    (the reference's zero-padded context rows)."""
    x = ins["X"][0]  # [B, T, D]
    w = ins["Filter"][0]
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    ctx_len = int(attrs.get("contextLength", attrs.get("context_length", 3)))
    ctx_start = int(attrs.get("contextStart", attrs.get("context_start", -1)))
    b, t, d = x.shape
    if seq_len is not None:
        mask = (jnp.arange(t)[None, :] < seq_len.reshape(-1, 1)).astype(x.dtype)
        x = x * mask[:, :, None]
    cols = []
    for k in range(ctx_len):
        off = ctx_start + k
        shifted = jnp.roll(x, -off, axis=1)
        pos = jnp.arange(t) + off
        valid = ((pos >= 0) & (pos < t)).astype(x.dtype)
        cols.append(shifted * valid[None, :, None])
    ctx_mat = jnp.concatenate(cols, axis=-1)  # [B, T, ctx_len*D]
    return {"Out": [ctx_mat @ w]}


@register("attention_lstm")
def _attention_lstm(ctx, ins, attrs):
    """Fused attention LSTM (attention_lstm_op.cc): at every output step,
    score each source position with fc([x_t_src ; h_prev]), softmax over
    the (length-masked) sequence, take the context vector, run one LSTM
    cell on it.  Padded [B, T, M] re-expression of the LoD original."""
    x = ins["X"][0]  # [B, T, M]
    h0 = ins["H0"][0] if ins.get("H0") else None
    c0 = ins["C0"][0]
    att_w = ins["AttentionWeight"][0]  # [M + D, 1]
    att_b = ins["AttentionBias"][0] if ins.get("AttentionBias") else None
    lstm_w = ins["LSTMWeight"][0]  # [M + D, 4D]
    lstm_b = ins["LSTMBias"][0] if ins.get("LSTMBias") else None
    seq_len = ins["SeqLen"][0] if ins.get("SeqLen") else None
    b, t, m = x.shape
    dd = c0.shape[-1]
    if h0 is None:
        h0 = jnp.zeros_like(c0)

    neg = jnp.asarray(-1e9, x.dtype)
    if seq_len is not None:
        pad = jnp.arange(t)[None, :] >= seq_len.reshape(-1, 1)
    else:
        pad = jnp.zeros((b, t), bool)

    def step(carry, _):
        h, c = carry
        # attention scores over all T positions given h
        he = jnp.broadcast_to(h[:, None, :], (b, t, dd))
        feat = jnp.concatenate([x, he], axis=-1)  # [B, T, M+D]
        score = (feat @ att_w)[..., 0]
        if att_b is not None:
            score = score + att_b.reshape(-1)[0]
        score = jnp.where(pad, neg, score)
        alpha = jax.nn.softmax(score, axis=-1)
        ctx_vec = jnp.einsum("bt,btm->bm", alpha, x)
        gin = jnp.concatenate([ctx_vec, h], axis=-1) @ lstm_w
        if lstm_b is not None:
            gin = gin + lstm_b.reshape(1, -1)
        i, f, cc, o = jnp.split(gin, 4, axis=-1)
        c_new = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(cc)
        h_new = jax.nn.sigmoid(o) * jnp.tanh(c_new)
        return (h_new, c_new), h_new

    (h_fin, c_fin), hs = jax.lax.scan(step, (h0, c0), None, length=t)
    return {
        "Hidden": [jnp.swapaxes(hs, 0, 1)],  # [B, T, D]
        "Cell": [c_fin],
        "LastH": [h_fin],
    }


@register("conv3d_transpose")
def _conv3d_transpose(ctx, ins, attrs):
    """conv3d_transpose_op: NCDHW transposed convolution via
    lax.conv_transpose (gradient-of-conv semantics on the MXU)."""
    x = ins["Input"][0]  # [N, C, D, H, W]
    w = ins["Filter"][0]  # [Cin, Cout, kD, kH, kW]
    strides = tuple(attrs.get("strides", [1, 1, 1]))
    pads = attrs.get("paddings", [0, 0, 0])
    dilations = list(attrs.get("dilations", [1, 1, 1]))
    groups = int(attrs.get("groups", 1) or 1)
    # paddle out = (D-1)*s - 2p + d*(k-1) + 1: jax pads the dilated input,
    # so each side takes d*(k-1) - p (see conv2d_transpose)
    jpads = [
        (dilations[i] * (w.shape[2 + i] - 1) - pads[i],) * 2 for i in range(3)
    ]

    def one(xg, wg):
        return jax.lax.conv_transpose(
            xg,
            wg,  # [Cin, Cout/g, kD, kH, kW]; Cin labeled 'O'
            strides,
            jpads,
            rhs_dilation=dilations,
            dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
            transpose_kernel=True,
        )

    if groups == 1:
        out = one(x, w)
    else:
        cin = x.shape[1] // groups
        out = jnp.concatenate(
            [
                one(x[:, g * cin:(g + 1) * cin], w[g * cin:(g + 1) * cin])
                for g in range(groups)
            ],
            axis=1,
        )
    return {"Output": [out]}


@register("max_pool3d_with_index")
def _max_pool3d_with_index(ctx, ins, attrs):
    """pool_with_index_op 3-D variant: max pool + flat d*h*w argmax mask."""
    x = ins["X"][0]  # [N, C, D, H, W]
    ks = [int(k) for k in attrs.get("ksize", [2, 2, 2])]
    st = [int(s) for s in attrs.get("strides", ks)]
    n, c, d, h, w = x.shape
    kd, kh, kw = ks
    sd, sh, sw = st
    od, oh, ow = (d - kd) // sd + 1, (h - kh) // sh + 1, (w - kw) // sw + 1
    patches = jax.lax.conv_general_dilated_patches(
        x.reshape(n * c, 1, d, h, w),
        (kd, kh, kw),
        (sd, sh, sw),
        "VALID",
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )  # [n*c, kd*kh*kw, od, oh, ow]
    patches = patches.reshape(n, c, kd * kh * kw, od, oh, ow)
    out = jnp.max(patches, axis=2)
    arg = jnp.argmax(patches, axis=2)
    wd = arg // (kh * kw)
    rem = arg % (kh * kw)
    wy, wx = rem // kw, rem % kw
    oz = jnp.arange(od).reshape(1, 1, -1, 1, 1)
    oy = jnp.arange(oh).reshape(1, 1, 1, -1, 1)
    ox = jnp.arange(ow).reshape(1, 1, 1, 1, -1)
    flat = ((oz * sd + wd) * h + (oy * sh + wy)) * w + (ox * sw + wx)
    return {"Out": [out], "Mask": [flat.astype(jnp.int32)]}


@register("data_norm")
def _data_norm(ctx, ins, attrs):
    """data_norm_op.cc: normalization by accumulated batch statistics
    (CTR models): means = BatchSum/BatchSize, scales =
    sqrt(BatchSize / BatchSquareSum); training also emits updated
    accumulators for the current minibatch."""
    x = ins["X"][0]  # [B, D]
    bsz = ins["BatchSize"][0]
    bsum = ins["BatchSum"][0]
    bsq = ins["BatchSquareSum"][0]
    eps = float(attrs.get("epsilon", 1e-4))
    means = bsum / jnp.maximum(bsz, 1.0)
    scales = jnp.sqrt(jnp.maximum(bsz, 1.0) / jnp.maximum(bsq, eps))
    out = (x - means.reshape(1, -1)) * scales.reshape(1, -1)
    nb = x.shape[0]
    upd_size = bsz + nb
    upd_sum = bsum + jnp.sum(x, axis=0)
    upd_sq = bsq + jnp.sum(x * x, axis=0)
    return {
        "Y": [out],
        "Means": [means],
        "Scales": [scales],
        "BatchSizeOut": [upd_size],
        "BatchSumOut": [upd_sum],
        "BatchSquareSumOut": [upd_sq],
    }


@register("seq_cache_write", no_grad_inputs=("Pos",))
def _seq_cache_write(ctx, ins, attrs):
    """KV-cache update for incremental decode: write the current chunk's
    [B, H, W, D] projections into the [B, H, T, D] cache at time indices
    Pos..Pos+W-1 (W == 1 is the classic one-token step; W > 1 is the
    chunked-prefill write).  Static shapes — one dynamic_update_slice on
    the time axis.  NB dynamic_update_slice CLAMPS Pos to T-W; callers
    validate lengths up front (decode_cache.validate_cached_call)."""
    cache, new, pos = ins["Cache"][0], ins["New"][0], ins["Pos"][0]
    pos = pos.reshape(()).astype(jnp.int32)
    zero = jnp.int32(0)
    return {"Out": [jax.lax.dynamic_update_slice(
        cache, new.astype(cache.dtype), (zero, zero, pos, zero))]}


@register("slot_cache_write", no_grad_inputs=("Pos", "Width"))
def _slot_cache_write(ctx, ins, attrs):
    """PER-ROW ragged KV-cache update (the continuous-batching serving
    step): write New [B, H, W, D] into Cache [B, H, T, D] where row b's
    column i lands at time index Pos[b] + i, but ONLY for i < Width[b]
    — a decoding slot writes one token (Width 1), a prefilling slot a
    whole chunk (Width <= W), a free slot nothing (Width 0).  Invalid
    columns (beyond Width, or past the cache) are DROPPED, never
    clamped: a clamp would silently overwrite a neighbor request's live
    keys, which is exactly the cross-request interference the serving
    exactness contract forbids."""
    cache, new = ins["Cache"][0], ins["New"][0]
    pos = ins["Pos"][0].reshape(-1).astype(jnp.int32)
    width = ins["Width"][0].reshape(-1).astype(jnp.int32)
    t_max = cache.shape[2]
    w = new.shape[2]
    col = jnp.arange(w, dtype=jnp.int32)
    idx = pos[:, None] + col[None, :]  # [B, W]
    valid = (col[None, :] < width[:, None]) & (idx < t_max)
    # out-of-bounds index == dropped under mode="drop": route every
    # invalid column to t_max
    idx = jnp.where(valid, idx, t_max)

    # GSPMD serving mesh: the write indexes the TIME axis only, so a
    # heads-axis-sharded pool updates shard-locally; the constraints pin
    # that placement (without them the partitioner may round-trip the
    # whole pool through a replicated scatter)
    sh = None
    from ..parallel.partition_rules import current_spmd

    spmd = current_spmd()
    if spmd is not None:
        from ..parallel.mesh import mesh_axis_sizes

        mesh, rules = spmd
        nsh = mesh_axis_sizes(mesh).get(rules.mp_axis, 1)
        if nsh > 1 and cache.shape[1] % nsh == 0:
            from jax.sharding import NamedSharding, PartitionSpec as P

            sh = NamedSharding(mesh,
                               P(None, rules.mp_axis, None, None))
            cache = jax.lax.with_sharding_constraint(cache, sh)
            new = jax.lax.with_sharding_constraint(new, sh)

    def row(c, n, i):
        # c [H, T, D], n [H, W, D], i [W]
        return c.at[:, i, :].set(n, mode="drop")

    out = jax.vmap(row)(cache, new.astype(cache.dtype), idx)
    if sh is not None:
        out = jax.lax.with_sharding_constraint(out, sh)
    return {"Out": [out]}


@register("decode_pos_mask", no_grad_inputs=("Pos",))
def _decode_pos_mask(ctx, ins, attrs):
    """[B, T] additive key bias for cached decode: 0 for key positions
    <= Pos, -1e30 beyond — the dynamic-length mask fused_attention's
    rank-1 Bias slot consumes."""
    pos = ins["Pos"][0].reshape(()).astype(jnp.int32)
    t = int(attrs["t_max"])
    b = int(attrs["batch"])
    row = jnp.where(jnp.arange(t, dtype=jnp.int32) <= pos, 0.0, -1e30)
    return {"Out": [jnp.broadcast_to(row[None, :], (b, t)).astype(jnp.float32)]}


def _pairing_permutation(dh):
    """P [Dh, Dh] of zeros and ones with x @ P = concatenate([x[..., 0::2],
    x[..., 1::2]], -1): input lane `src[i]` lands on output lane i."""
    src = np.concatenate([np.arange(0, dh, 2), np.arange(1, dh, 2)])
    perm = np.zeros((dh, dh), np.float32)
    perm[src, np.arange(dh)] = 1
    return perm


def _deinterleave(x):
    """Published pairs (2i, 2i+1) of the last axis -> (i, i + Dh/2): what
    concatenate([x[..., 0::2], x[..., 1::2]], -1) gives, as x @ P with P a
    constant permutation, so that nothing strided moves along the lane
    axis: JAX lowers that index to a gather and its transpose to a
    scatter-add (`lax.slice` strides: to an interior pad), and a TPU step
    paid for them in copies around each (PERF.md section 6, PR 43).  Exact
    in every dtype: each output is one input times 1 plus zeros.  A float32
    product asks for HIGHEST, at which the MXU multiplies all three
    bfloat16 pieces of a value (by 1: they sum back to it); at the default
    it would round x to bfloat16.  The VJP is the product with P's
    transpose at the same precision.  One departure from the index: a NaN
    or an infinity in x reaches every lane of its own row of Dh (0 x inf),
    not only its own."""
    perm = _pairing_permutation(x.shape[-1])
    return jnp.matmul(x, jnp.asarray(perm, x.dtype),
                      precision=jax.lax.Precision.HIGHEST)


def yarn_correction_range(dim, base, original_max_position, beta_fast,
                          beta_slow):
    """(low, high): the rotary pairs of a head `dim` wide between which YaRN
    ramps from the published frequencies to the interpolated ones: the pair
    that turns `beta` times over `original_max_position` positions is
    dim ln(original / (beta 2 pi)) / (2 ln base); low is the floor of
    beta_fast's, high the ceiling of beta_slow's, both clipped to
    [0, dim - 1] (the transformers convention the config keys are named
    after)."""
    def pair(turns):
        return (dim * math.log(original_max_position / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    return (max(math.floor(pair(beta_fast)), 0),
            min(math.ceil(pair(beta_slow)), dim - 1))


def _rotary_inv_freq(half, base, attrs):
    """[half] float32 inverse frequencies of a head 2 * half wide: base^(-i
    / half), or, where the op carries `yarn_factor`, YaRN's static blend of
    them with the same divided by the factor: pair i keeps its frequency
    below `low`, takes the interpolated one above `high`, and between them
    r_i = (i - low) / (high - low) of the way."""
    pos_freq = base ** (jnp.arange(half, dtype=jnp.float32) / half)
    factor = attrs.get("yarn_factor")
    if not factor:
        return 1.0 / pos_freq
    low, high = yarn_correction_range(
        2 * half, base, float(attrs["yarn_original_max_position"]),
        float(attrs["yarn_beta_fast"]), float(attrs["yarn_beta_slow"]))
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return (1.0 - ramp) / pos_freq + ramp / (float(factor) * pos_freq)


@register("rotary_embed", no_grad_inputs=("Pos",))
def _rotary_embed(ctx, ins, attrs):
    """Rotary position embedding (RoPE, rotate-half convention) applied
    to per-head projections [B, H, T, Dh].  Pos: optional int positions
    [T] (defaults to arange(T)); the cached decode path feeds the single
    current position so cache-resident keys are stored pre-rotated.
    Attribute `interleaved` (False): the input's pairs are (2i, 2i+1), as
    DeepSeek-V3's weights are published; it is de-interleaved first and
    the result is left in the rotate-half order.  The de-interleave is a
    product with a constant Dh x Dh permutation matrix (`_deinterleave`),
    bit for bit what two lane-strided slices give, on the MXU.
    Attributes `yarn_factor`, `yarn_original_max_position`,
    `yarn_beta_fast`, `yarn_beta_slow`: YaRN's scaled inverse frequencies
    (`_rotary_inv_freq`), static, computed in float32 at trace time;
    `attention_factor` multiplies cos and sin (so every rotated lane of q
    and of k).  Positions arange(T) alone: the cached decode path's `Pos`
    is refused with them.
    Beyond-reference (the reference era used learned/sinusoid absolute
    positions); standard in modern decoder LMs."""
    x = ins["X"][0]
    base = float(attrs.get("base", 10000.0))
    amplitude = float(attrs.get("attention_factor", 1.0))
    scaled = bool(attrs.get("yarn_factor")) or amplitude != 1.0
    if scaled and ins.get("Pos"):
        raise NotImplementedError(
            "rotary_embed: scaled frequencies (yarn_factor / "
            "attention_factor) are the training path's; the cached decode "
            "path, which feeds Pos, has none yet")
    t = x.shape[2]
    if x.shape[-1] % 2:
        raise ValueError(
            "rotary_embed: head dim must be even (rotate-half pairs), "
            "got %d" % x.shape[-1])
    half = x.shape[-1] // 2
    if attrs.get("interleaved", False):
        x = _deinterleave(x)  # the result stays in that order
    if scaled:
        freq = _rotary_inv_freq(half, base, attrs)
    else:  # as every program before had it, to the instruction
        freq = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    if ins.get("Pos") and ins["Pos"][0].ndim == 2:
        # PER-ROW positions [B, T] (ragged serving step: each pool slot
        # rotates by its own request's positions)
        pos = ins["Pos"][0].astype(jnp.float32)
        ang = pos[:, :, None] * freq[None, None, :]  # [B, T, half]
        sin = jnp.sin(ang)[:, None].astype(x.dtype)  # [B, 1, T, half]
        cos = jnp.cos(ang)[:, None].astype(x.dtype)
        x1, x2 = x[..., :half], x[..., half:]
        return {"Out": [jnp.concatenate(
            [x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)]}
    if ins.get("Pos"):
        pos = ins["Pos"][0].reshape(-1).astype(jnp.float32)
    else:
        pos = jnp.arange(t, dtype=jnp.float32)
    ang = pos[:, None] * freq[None, :]  # [T, half]

    def table(fn):  # [1, 1, T, half], times the attention factor
        t = fn(ang) if amplitude == 1.0 else fn(ang) * amplitude
        return t[None, None].astype(x.dtype)

    sin, cos = table(jnp.sin), table(jnp.cos)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)
    return {"Out": [out]}


# ---------------------------------------------------------------------------
# static infer rules (analysis/infer.py)
# ---------------------------------------------------------------------------
from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    numel_known,
    register_infer,
    same_as,
    same_dtype,
    slot_info as _vi,
)


def _conv_hw(dim, k, s, p, d, ceil_mode=False):
    if dim < 0:
        return -1
    eff = d * (k - 1) + 1
    num = dim + 2 * p - eff
    if num < 0:
        raise InferError(
            "conv/pool window (k=%d, dilation=%d) exceeds padded input "
            "dim %d" % (k, d, dim + 2 * p))
    if ceil_mode:
        return -(-num // s) + 1
    return num // s + 1


@register_infer("conv2d", req_ins=("Input", "Filter"), req_outs=("Output",))
@register_infer("depthwise_conv2d", req_ins=("Input", "Filter"),
                req_outs=("Output",))
def _conv2d_infer(op, ins):
    x, w = _vi(ins, "Input"), _vi(ins, "Filter")
    if x is None or x.shape is None or w is None or w.shape is None:
        return {}
    if len(x.shape) != 4 or len(w.shape) != 4:
        raise InferError(
            "conv2d expects rank-4 Input/Filter, got %s / %s"
            % (x.shape, w.shape))
    a = op.attrs
    strides = _pair(a.get("strides", [1, 1]))
    pads = _pair(a.get("paddings", [0, 0]))
    dils = _pair(a.get("dilations", [1, 1]))
    nhwc = a.get("data_format", "NCHW") == "NHWC"
    h_ax, w_ax, c_ax = (1, 2, 3) if nhwc else (2, 3, 1)
    groups = a.get("groups", 1) or 1
    if op.type == "depthwise_conv2d":
        groups = x.shape[c_ax] if x.shape[c_ax] >= 0 else groups
    cin = x.shape[c_ax]
    if cin >= 0 and w.shape[1] >= 0 and groups and cin != w.shape[1] * groups:
        raise InferError(
            "conv2d channel mismatch: input C=%d vs Filter[1]*groups=%d*%d"
            % (cin, w.shape[1], groups))
    oh = _conv_hw(x.shape[h_ax], w.shape[2], strides[0], pads[0], dils[0])
    ow = _conv_hw(x.shape[w_ax], w.shape[3], strides[1], pads[1], dils[1])
    shape = [x.shape[0], 0, 0, 0]
    shape[h_ax], shape[w_ax], shape[c_ax] = oh, ow, w.shape[0]
    return {"Output": [VarInfo(tuple(shape), x.dtype)]}


@register_infer("pool2d", req_ins=("X",))
def _pool2d_infer(op, ins):
    x = _vi(ins, "X")
    if x is None or x.shape is None:
        return {}
    if len(x.shape) != 4:
        raise InferError("pool2d expects rank-4 input, got %s" % (x.shape,))
    a = op.attrs
    nhwc = a.get("data_format", "NCHW") == "NHWC"
    sp = (1, 2) if nhwc else (2, 3)
    shape = list(x.shape)
    if a.get("global_pooling", False) or (
            a.get("adaptive", False) and list(a.get("ksize")) == [1, 1]):
        shape[sp[0]] = shape[sp[1]] = 1
        return {"Out": [VarInfo(tuple(shape), x.dtype)]}
    ksize = _pair(a.get("ksize", [2, 2]))
    strides = _pair(a.get("strides", [1, 1]))
    pads = _pair(a.get("paddings", [0, 0]))
    ceil = bool(a.get("ceil_mode", False))
    shape[sp[0]] = _conv_hw(x.shape[sp[0]], ksize[0], strides[0], pads[0],
                            1, ceil)
    shape[sp[1]] = _conv_hw(x.shape[sp[1]], ksize[1], strides[1], pads[1],
                            1, ceil)
    return {"Out": [VarInfo(tuple(shape), x.dtype)]}


@register_infer("batch_norm", req_ins=("X", "Scale", "Bias", "Mean",
                                       "Variance"), req_outs=("Y",))
def _bn_infer(op, ins):
    x, mean = _vi(ins, "X"), _vi(ins, "Mean")
    xi = VarInfo(x.shape, x.dtype) if x is not None else None
    stat = VarInfo(mean.shape, None) if mean is not None else None
    return {
        "Y": [xi],
        "MeanOut": [stat], "VarianceOut": [stat],
        "SavedMean": [stat], "SavedVariance": [stat],
    }


@register_infer("layer_norm", req_ins=("X",), req_outs=("Y",))
def _ln_infer(op, ins):
    x = _vi(ins, "X")
    if x is None:
        return {}
    begin = int(op.attrs.get("begin_norm_axis", 1))
    stat = None
    if x.shape is not None:
        stat = VarInfo(x.shape[:begin], None)
    return {"Y": [VarInfo(x.shape, x.dtype)],
            "Mean": [stat], "Variance": [stat]}


@register_infer("rms_norm", req_ins=("X", "Scale"), req_outs=("Y",))
def _rms_infer(op, ins):
    x, w = _vi(ins, "X"), _vi(ins, "Scale")
    if x is None:
        return {}
    # the gain covers the last axis, or the last few (a gain a group)
    if (x.shape is not None and w is not None and w.shape is not None
            and x.shape[-1] >= 0 and not (
                0 < len(w.shape) < len(x.shape)
                and tuple(w.shape) == tuple(x.shape[-len(w.shape):]))):
        raise InferError(
            "rms_norm Scale%s does not match X%s's last axis"
            % (w.shape, x.shape))
    return {"Y": [VarInfo(x.shape, x.dtype)]}


@register_infer("short_conv", req_ins=("BCX", "Filter"), req_outs=("Out",))
def _short_conv_infer(op, ins):
    x, k = _vi(ins, "BCX"), _vi(ins, "Filter")
    if x is None or x.shape is None:
        return {}
    if len(x.shape) < 2:
        raise InferError("short_conv wants BCX [..., T, 3d], got %s"
                         % (x.shape,))
    width = x.shape[-1]
    if k is not None and k.shape is not None:
        if len(k.shape) != 2 or (width >= 0 and width != 3 * k.shape[0]):
            raise InferError(
                "short_conv Filter%s does not match BCX%s (want [d, L] "
                "against [..., T, 3d])" % (k.shape, x.shape))
        width = k.shape[0]
    elif width >= 0:
        width //= 3
    return {"Out": [VarInfo(tuple(x.shape[:-1]) + (width,), x.dtype)]}


@register_infer("causal_conv", req_ins=("X", "Filter"), req_outs=("Out",))
def _causal_conv_infer(op, ins):
    x, k = _vi(ins, "X"), _vi(ins, "Filter")
    if x is None or x.shape is None:
        return {}
    if len(x.shape) < 2:
        raise InferError("causal_conv wants X [..., T, d], got %s"
                         % (x.shape,))
    if (k is not None and k.shape is not None
            and (len(k.shape) != 2
                 or (x.shape[-1] >= 0 and x.shape[-1] != k.shape[0]))):
        raise InferError("causal_conv Filter%s does not match X%s (want "
                         "[d, L] against [..., T, d])" % (k.shape, x.shape))
    bias = _vi(ins, "Bias")
    if (bias is not None and bias.shape is not None and x.shape[-1] >= 0
            and tuple(bias.shape) != (x.shape[-1],)):
        raise InferError("causal_conv Bias%s is not [%d]: one number a "
                         "channel" % (bias.shape, x.shape[-1]))
    return {"Out": [VarInfo(x.shape, x.dtype)]}


@register_infer("dropout", req_ins=("X",))
def _dropout_infer(op, ins):
    x = _vi(ins, "X")
    xi = VarInfo(x.shape, x.dtype) if x is not None else None
    return {"Out": [xi], "Mask": [xi]}


@register_infer("fc", req_ins=("Input", "W"))
def _fc_infer(op, ins):
    x, w = _vi(ins, "Input"), _vi(ins, "W")
    if x is None or x.shape is None or w is None or w.shape is None:
        return {"Out": [VarInfo(None, same_dtype(x, w))]}
    k = int(op.attrs.get("in_num_col_dims", 1))
    xk = numel_known(x.shape[k:])
    if (len(w.shape) == 2 and xk is not None and w.shape[0] >= 0
            and xk != w.shape[0]):
        raise InferError(
            "fc contraction mismatch: Input%s flattens to K=%d but W%s "
            "expects K=%d" % (x.shape, xk, w.shape, w.shape[0]))
    return {"Out": [VarInfo(tuple(x.shape[:k]) + (w.shape[-1],),
                            same_dtype(x, w))]}


@register_infer("fused_swiglu", req_ins=("X", "GateW", "UpW"))
def _swiglu_infer(op, ins):
    x, wg = _vi(ins, "X"), _vi(ins, "GateW")
    if x is None or x.shape is None or wg is None or wg.shape is None:
        return {}
    k = int(op.attrs.get("x_num_col_dims", 1))
    return {"Out": [VarInfo(tuple(x.shape[:k]) + (wg.shape[-1],),
                            same_dtype(x, wg))]}


@register_infer("fused_residual_ln", req_ins=("X", "Y", "Scale", "Bias"),
                req_outs=("Y", "Sum"))
def _frln_infer(op, ins):
    x = _vi(ins, "X")
    if x is None:
        return {}
    xi = VarInfo(x.shape, x.dtype)
    stat = VarInfo(x.shape[:-1], None) if x.shape is not None else None
    return {"Sum": [xi], "Y": [xi], "Mean": [stat], "Variance": [stat]}


@register_infer("fused_attention", req_ins=("Q", "K", "V"))
def _fattn_infer(op, ins):
    q, k, v = _vi(ins, "Q"), _vi(ins, "K"), _vi(ins, "V")
    try:
        layout = attention_layout(op.attrs)
    except ValueError as e:
        raise InferError(str(e))
    want = "[B, H, T, D]" if layout == "bhtd" else "[B, T, H, D]"
    for name, t in (("Q", q), ("K", k), ("V", v)):
        if t is not None and t.shape is not None and len(t.shape) != 4:
            raise InferError(
                "fused_attention %s must be rank-4 %s, got %s"
                % (name, want, t.shape))
    if (q is not None and k is not None and q.shape is not None
            and k.shape is not None and q.shape[-1] >= 0
            and k.shape[-1] >= 0 and q.shape[-1] != k.shape[-1]):
        raise InferError(
            "fused_attention head-dim mismatch: Q%s vs K%s"
            % (q.shape, k.shape))
    # Q's shape at V's width ([B, H, Tq, d_v], or [B, Tq, H, d_v] under
    # "bthd"): V's width is Q's everywhere but under latent attention
    shape = q.shape if q else None
    if shape is not None and v is not None and v.shape is not None:
        shape = tuple(shape[:-1]) + (v.shape[-1],)
    return {"Out": [VarInfo(shape, q.dtype if q else None)]}


register_infer("seq_cache_write", req_ins=("Cache", "New", "Pos"))(
    same_as("Cache"))
register_infer("slot_cache_write",
               req_ins=("Cache", "New", "Pos", "Width"))(same_as("Cache"))
register_infer("rotary_embed", req_ins=("X",))(same_as("X"))


@register_infer("decode_pos_mask", req_ins=("Pos",))
def _dpm_infer(op, ins):
    return {"Out": [VarInfo(
        (int(op.attrs["batch"]), int(op.attrs["t_max"])), "float32")]}
