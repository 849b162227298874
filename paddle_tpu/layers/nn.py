"""Neural-net layers (python/paddle/fluid/layers/nn.py analog).

Each function appends ops to the current program block via LayerHelper —
same graph-building contract as the reference (nn.py:174 fc, :283 embedding,
:1524 conv2d, :2290 batch_norm ...), with lowerings that compile to
MXU-friendly XLA ops.
"""

import math

import numpy as np

from .. import framework, unique_name
from ..framework import Variable
from ..initializer import Constant, Normal, Xavier
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = [
    "conv3d_transpose",
    "data_norm",

    "fused_attention",
    "slot_cache_write",
    "rotary_embed",
    "log_loss",
    "beam_search",
    "beam_search_decode",
    "fc",
    "embedding",
    "conv2d",
    "conv3d",
    "conv2d_transpose",
    "depthwise_conv2d",
    "pool2d",
    "adaptive_pool2d",
    "batch_norm",
    "layer_norm",
    "rms_norm",
    "short_conv",
    "causal_conv",
    "kda_attention",
    "gated_delta_attention",
    "mamba2_scan",
    "moe_ffn",
    "group_norm",
    "instance_norm",
    "dropout",
    "softmax",
    "log_softmax",
    "cross_entropy",
    "softmax_with_cross_entropy",
    "sigmoid_cross_entropy_with_logits",
    "square_error_cost",
    "smooth_l1",
    "huber_loss",
    "label_smooth",
    "mean",
    "mul",
    "matmul",
    "dot",
    "reduce_sum",
    "reduce_mean",
    "reduce_max",
    "reduce_min",
    "reduce_prod",
    "reshape",
    "transpose",
    "flatten",
    "squeeze",
    "unsqueeze",
    "split",
    "slice",
    "expand",
    "stack",
    "unstack",
    "topk",
    "one_hot",
    "l2_normalize",
    "clip",
    "clip_by_norm",
    "elementwise_add",
    "elementwise_sub",
    "elementwise_mul",
    "elementwise_div",
    "elementwise_max",
    "elementwise_min",
    "elementwise_pow",
    "pad",
    "pad2d",
    "prelu",
    "maxout",
    "relu",
    "lrn",
    "resize_bilinear",
    "resize_nearest",
    "image_resize",
    "gather",
    "gather_nd",
    "scatter",
    "shape",
    "gaussian_random",
    "uniform_random",
    "uniform_random_batch_size_like",
    "gaussian_random_batch_size_like",
    "sampling_id",
    "dynamic_lstm",
    "dynamic_gru",
    "lstm",
    "gru",
    "sum",
    "cos_sim",
    "pow",
    "scale",
    "hard_sigmoid",
    "swish",
    "leaky_relu",
    "elu",
    "relu6",
    "pixel_shuffle",
    "where",
    "cond_take",
    "unfold",
    "increment",
    "cumsum",
]


def _helper_out(helper, dtype=None):
    return helper.create_variable_for_type_inference(dtype or helper.input_dtype())


def _simple(op_type, x, attrs=None, name=None, out_dtype=None, x_slot="X", out_slot="Out"):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(out_dtype or x.dtype)
    helper.append_op(
        op_type, inputs={x_slot: [x]}, outputs={out_slot: [out]}, attrs=attrs or {}
    )
    return out


def fc(
    input,
    size,
    num_flatten_dims=1,
    param_attr=None,
    bias_attr=None,
    act=None,
    is_test=False,
    name=None,
):
    """Fully-connected (nn.py:174 parity): per input a mul op, summed, bias,
    activation. Lowered to one MXU matmul per input."""
    helper = LayerHelper("fc", **locals())
    dtype = helper.input_dtype()
    mul_results = []
    for input_var, param_attr_ in zip(
        helper.multiple_input(), helper.multiple_param_attr(len(helper.multiple_input()))
    ):
        input_shape = input_var.shape
        param_shape = [
            int(np.prod(input_shape[num_flatten_dims:]))
        ] + [size]
        w = helper.create_parameter(
            attr=param_attr_, shape=param_shape, dtype=dtype, is_bias=False
        )
        tmp = helper.create_variable_for_type_inference(dtype)
        helper.append_op(
            "mul",
            inputs={"X": [input_var], "Y": [w]},
            outputs={"Out": [tmp]},
            attrs={"x_num_col_dims": num_flatten_dims, "y_num_col_dims": 1},
        )
        mul_results.append(tmp)
    if len(mul_results) == 1:
        pre_bias = mul_results[0]
    else:
        pre_bias = helper.create_variable_for_type_inference(dtype)
        helper.append_op("sum", inputs={"X": mul_results}, outputs={"Out": [pre_bias]})
    pre_act = helper.append_bias_op(pre_bias, dim_start=num_flatten_dims)
    return helper.append_activation(pre_act)


def embedding(
    input,
    size,
    is_sparse=False,
    is_distributed=False,
    padding_idx=None,
    param_attr=None,
    dtype="float32",
):
    """Embedding lookup (nn.py:283). is_sparse/is_distributed are accepted
    for API parity; on TPU the lookup compiles to a gather and the gradient
    to a scatter-add (the SelectedRows path is unnecessary under XLA)."""
    helper = LayerHelper("embedding", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=size, dtype=dtype, is_bias=False
    )
    tmp = helper.create_variable_for_type_inference(dtype)
    padding_idx = (
        -1 if padding_idx is None else padding_idx if padding_idx >= 0 else size[0] + padding_idx
    )
    helper.append_op(
        "lookup_table",
        inputs={"W": [w], "Ids": [input]},
        outputs={"Out": [tmp]},
        attrs={
            "padding_idx": padding_idx,
            "is_sparse": bool(is_sparse),
            # consumed by DistributeTranspiler._handle_distributed_lookup:
            # rows shard over pservers, forward becomes a prefetch op
            "is_distributed": bool(is_distributed),
        },
    )
    return tmp


def conv2d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    """conv2d (nn.py:1524). use_cudnn accepted for parity; lowering always
    targets the MXU via lax.conv_general_dilated."""
    helper = LayerHelper("conv2d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    if isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    filter_shape = [num_filters, num_channels // groups] + list(filter_size)
    std = (2.0 / (filter_size[0] * filter_size[1] * num_channels)) ** 0.5
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=filter_shape,
        dtype=dtype,
        default_initializer=Normal(0.0, std),
    )
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv2d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def depthwise_conv2d(input, num_filters, filter_size, **kwargs):
    kwargs["groups"] = input.shape[1]
    return conv2d(input, num_filters, filter_size, **kwargs)


def conv3d(
    input,
    num_filters,
    filter_size,
    stride=1,
    padding=0,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv3d", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1

    def _trip(v):
        return [v] * 3 if isinstance(v, int) else list(v)

    filter_size, stride, padding, dilation = map(
        _trip, (filter_size, stride, padding, dilation)
    )
    filter_shape = [num_filters, num_channels // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv3d",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def conv2d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    helper = LayerHelper("conv2d_transpose", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1
    if isinstance(stride, int):
        stride = [stride, stride]
    if isinstance(padding, int):
        padding = [padding, padding]
    if isinstance(dilation, int):
        dilation = [dilation, dilation]
    if filter_size is None:
        h, w_ = input.shape[2], input.shape[3]
        oh, ow = output_size if isinstance(output_size, (list, tuple)) else (output_size, output_size)
        filter_size = [
            oh - (h - 1) * stride[0] + 2 * padding[0],
            ow - (w_ - 1) * stride[1] + 2 * padding[1],
        ]
    elif isinstance(filter_size, int):
        filter_size = [filter_size, filter_size]
    filter_shape = [num_channels, num_filters // groups] + list(filter_size)
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv2d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def pool2d(
    input,
    pool_size=-1,
    pool_type="max",
    pool_stride=1,
    pool_padding=0,
    global_pooling=False,
    use_cudnn=True,
    ceil_mode=False,
    exclusive=True,
    name=None,
):
    helper = LayerHelper("pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if isinstance(pool_size, int):
        pool_size = [pool_size, pool_size]
    if isinstance(pool_stride, int):
        pool_stride = [pool_stride, pool_stride]
    if isinstance(pool_padding, int):
        pool_padding = [pool_padding, pool_padding]
    helper.append_op(
        "pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={
            "pooling_type": pool_type,
            "ksize": pool_size,
            "strides": pool_stride,
            "paddings": pool_padding,
            "global_pooling": global_pooling,
            "ceil_mode": ceil_mode,
            "exclusive": exclusive,
        },
    )
    return out


def adaptive_pool2d(input, pool_size, pool_type="max", name=None):
    helper = LayerHelper("adaptive_pool2d", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "adaptive_pool2d",
        inputs={"X": [input]},
        outputs={"Out": [out]},
        attrs={"ksize": list(pool_size), "pooling_type": pool_type},
    )
    return out


def batch_norm(
    input,
    act=None,
    is_test=False,
    momentum=0.9,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    data_layout="NCHW",
    in_place=False,
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    do_model_average_for_mean_and_var=False,
    use_global_stats=False,
):
    """batch_norm (nn.py:2290): creates scale/bias params + persistable
    moving mean/variance; training mode updates the moving stats in the same
    compiled step (functionalized in-place outputs)."""
    helper = LayerHelper("batch_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    param_shape = [channels]

    scale = helper.create_parameter(
        attr=helper.param_attr,
        shape=param_shape,
        dtype=dtype,
        default_initializer=Constant(1.0),
    )
    bias = helper.create_parameter(
        attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
    )
    mean = helper.create_parameter(
        attr=ParamAttr(name=moving_mean_name, trainable=False),
        shape=param_shape,
        dtype=dtype,
        default_initializer=Constant(0.0),
    )
    mean.stop_gradient = True
    variance = helper.create_parameter(
        attr=ParamAttr(name=moving_variance_name, trainable=False),
        shape=param_shape,
        dtype=dtype,
        default_initializer=Constant(1.0),
    )
    variance.stop_gradient = True

    saved_mean = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    saved_variance = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)

    helper.append_op(
        "batch_norm",
        inputs={
            "X": [input],
            "Scale": [scale],
            "Bias": [bias],
            "Mean": [mean],
            "Variance": [variance],
        },
        outputs={
            "Y": [out],
            "MeanOut": [mean],
            "VarianceOut": [variance],
            "SavedMean": [saved_mean],
            "SavedVariance": [saved_variance],
        },
        attrs={
            "momentum": momentum,
            "epsilon": epsilon,
            "is_test": is_test,
            "data_layout": data_layout,
            "use_global_stats": use_global_stats,
        },
    )
    return helper.append_activation(out)


def layer_norm(
    input,
    scale=True,
    shift=True,
    begin_norm_axis=1,
    epsilon=1e-5,
    param_attr=None,
    bias_attr=None,
    act=None,
    name=None,
):
    helper = LayerHelper("layer_norm", **locals())
    dtype = helper.input_dtype()
    param_shape = [int(np.prod(input.shape[begin_norm_axis:]))]
    inputs = {"X": [input]}
    if scale:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=param_shape,
            dtype=dtype,
            default_initializer=Constant(1.0),
        )
        inputs["Scale"] = [s]
    if shift:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=param_shape, dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "layer_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"begin_norm_axis": begin_norm_axis, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def rms_norm(input, epsilon=1e-5, param_attr=None, name=None,
             unit_offset=False, gain_axes=1):
    """x * rsqrt(mean(x^2) + epsilon) * w over the last axis, w a [d]
    parameter initialised to 1 (no mean subtraction, no bias).
    `unit_offset`: the gain is 1 + w and w is initialised to 0 (the
    published Qwen3NextRMSNorm, Gemma's): a `scale` op adds the 1 to the
    parameter, the `rms_norm` op is the same.  `gain_axes` 2: the gain
    holds a number for every element of the last TWO axes ([groups, d]:
    Mamba-2's norm over groups of d_inner / n_groups channels, each group
    with gains of its own); the statistic stays the last axis's."""
    helper = LayerHelper("rms_norm", **locals())
    dtype = helper.input_dtype()
    w = helper.create_parameter(
        attr=helper.param_attr,
        shape=[int(n) for n in input.shape[-int(gain_axes):]], dtype=dtype,
        default_initializer=Constant(0.0 if unit_offset else 1.0))
    if unit_offset:
        w = scale(w, scale=1.0, bias=1.0)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("rms_norm", inputs={"X": [input], "Scale": [w]},
                     outputs={"Y": [out]}, attrs={"epsilon": epsilon})
    return out


def short_conv(input, kernel_size=3, param_attr=None, name=None):
    """Gated short convolution over the T axis of `input` [..., T, 3d]
    (the `short_conv` op): the last axis holds B, C and u side by side,
    the result [..., T, d] is C * causal_depthwise_conv(B * u) with one
    [kernel_size] filter a channel and no bias.  The projections on
    either side stay `fc` layers of the caller's."""
    helper = LayerHelper("short_conv", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1]) // 3
    if 3 * d != int(input.shape[-1]):
        raise ValueError("short_conv wants a last axis of 3 d, got %s"
                         % (input.shape,))
    filt = helper.create_parameter(
        attr=helper.param_attr, shape=[d, int(kernel_size)], dtype=dtype)
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("short_conv", inputs={"BCX": [input], "Filter": [filt]},
                     outputs={"Out": [out]})
    return out


def causal_conv(input, kernel_size=4, act=None, param_attr=None, name=None,
                bias_attr=None):
    """Depthwise causal convolution over the T axis of `input` [..., T, d]
    (the `causal_conv` op): one [kernel_size] filter a channel, zeros left
    of t = 0, no gate; `act` "silu" applies SiLU to the result inside the
    op.  Kimi Linear's q, k and v pass through one each.  `bias_attr`: a
    [d] bias (zero at initialisation) added before the activation (Mamba-2's
    `use_conv_bias`); None builds the op without one."""
    if act not in (None, "silu"):
        raise ValueError("causal_conv act %r is neither silu nor None"
                         % (act,))
    helper = LayerHelper("causal_conv", **locals())
    dtype = helper.input_dtype()
    filt = helper.create_parameter(
        attr=helper.param_attr, shape=[int(input.shape[-1]), int(kernel_size)],
        dtype=dtype)
    inputs = {"X": [input], "Filter": [filt]}
    if bias_attr is not None:
        inputs["Bias"] = [helper.create_parameter(
            attr=bias_attr, shape=[int(input.shape[-1])], dtype=dtype,
            is_bias=True)]
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op("causal_conv", inputs=inputs, outputs={"Out": [out]},
                     attrs={"act": act or ""})
    return out


def kda_attention(q, k, v, g, beta, scale=None, name=None):
    """Kimi Delta Attention over [batch, heads, T, d] (the `kda_attention`
    op, ops/kda_ops.py): per head a dk x dv state that every token decays
    channel by channel (`g` [batch, heads, T, dk] float32, the log-decay,
    <= 0), corrects by the delta rule with step `beta` [batch, heads, T]
    and reads with q times `scale` (dk^-0.5 where None).  The result is
    [batch, heads, T, dv] in v's dtype.  No initial state and no state
    handed back: the causal training path.  `g` is a decay of every key
    channel; one number a head a token (and key heads shared by several
    value heads) is `gated_delta_attention`'s."""
    helper = LayerHelper("kda_attention", **locals())
    out = helper.create_variable_for_type_inference(v.dtype)
    # Out is V's shape and dtype, said here: appended straight to the block
    # like fused_attention, the chunked lowering is never evaluated to
    # build a program
    helper.main_program.current_block().append_op(
        "kda_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]},
        attrs={"scale": None if scale is None else float(scale)})
    out.shape = tuple(v.shape)
    return out


def gated_delta_attention(q, k, v, g, beta, scale=None, name=None):
    """Gated DeltaNet's core (the `gated_delta_attention` op,
    ops/kda_ops.py): the delta rule with ONE decay a head.  `q`, `k`
    [batch, key heads, T, dk]; `v` [batch, value heads, T, dv]; `g` (the
    log-decay, <= 0) and `beta` (the rule's step) [batch, value heads, T]
    float32.  The key heads divide the value heads and value head j reads
    key head j // (value heads / key heads) (Qwen3-Next: 16 read by 32);
    q is multiplied by `scale` (dk^-0.5 where None).  The result is
    [batch, value heads, T, dv] in v's dtype.  No initial state and no
    state handed back: the causal training path.  A decay of every key
    CHANNEL is `kda_attention`'s."""
    helper = LayerHelper("gated_delta_attention", **locals())
    out = helper.create_variable_for_type_inference(v.dtype)
    helper.main_program.current_block().append_op(
        "gated_delta_attention",
        inputs={"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta]},
        outputs={"Out": [out]},
        attrs={"scale": None if scale is None else float(scale)})
    out.shape = tuple(v.shape)
    return out


def mamba2_scan(x, dt, a, b, c, d, name=None):
    """The Mamba-2 selective scan (the `mamba2_scan` op, ops/mamba2_ops.py):
    `x` [batch, heads, T, P]; `dt` [batch, heads, T] float32, the step of
    every token (after its softplus); `a` [heads] float32, one negative
    decay rate a head; `b`, `c` [batch, groups, T, N], the groups dividing
    the heads (head j reads group j // (heads / groups)); `d` [heads]
    float32, the skip.  Per head S_t = exp(dt_t a) S_{t-1} + dt_t x_t b_t^T
    and y_t = S_t c_t + d x_t from a zero state; the result is [batch,
    heads, T, P] in x's dtype.  No initial state and no state handed back:
    the causal training path."""
    helper = LayerHelper("mamba2_scan", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    # Out is X's shape and dtype, said here: appended straight to the block
    # like kda_attention, the chunked lowering is never evaluated to build
    # a program
    helper.main_program.current_block().append_op(
        "mamba2_scan",
        inputs={"X": [x], "Dt": [dt], "A": [a], "B": [b], "C": [c],
                "D": [d]},
        outputs={"Out": [out]}, attrs={})
    out.shape = tuple(x.shape)
    return out


def moe_ffn(input, num_experts, expert_size, top_k, norm_topk_prob=False,
            router_attr=None, gate_up_attr=None, down_attr=None,
            stat_name="moe_tokens_per_expert", name=None, router="softmax",
            expert_bias_attr=None, num_local_experts=None, expert_offset=0,
            routed_scaling_factor=1.0, norm_topk_eps=1e-6,
            expert_act="swiglu"):
    """Token-choice mixture of SwiGLU experts over the last axis of
    `input` (the `moe_ffn` op: top-k, dropless).  The experts' weights are
    stacked: gate and up side by side in one [E, d, 2 * expert_size]
    parameter, down in [E, expert_size, d].  `expert_act` "relu2" makes
    every expert the ungated down(relu(up x)^2) (Nemotron-H's): the first
    parameter is then the up weight alone, [E, d, expert_size].

    `router` is "softmax" (OLMoE's) or "sigmoid": scores sigmoid(logits),
    weights renormalised over the chosen with `norm_topk_eps` added to
    their sum under `norm_topk_prob` (LFM2 publishes 1e-6, the
    DeepSeek-V3 family 1e-20); with `expert_bias_attr` a [num_experts]
    f32 buffer (a parameter that is not trainable) is added to the scores
    for the selection alone.  `routed_scaling_factor` multiplies the
    chosen experts' weights after the renormalisation (either router; 1
    adds no instruction).  `num_local_experts` < `num_experts` builds a
    chip's share of the layer: the router stays [d, num_experts], the
    expert weights hold experts [expert_offset, expert_offset +
    num_local_experts), and what the others would add is left out.

    Returns (out, aux_loss, tokens_per_expert): aux_loss is [2] f32, the
    load-balance loss E * sum_e F_e * P_e and the router z-loss (zeros for
    the sigmoid router), for the builder to weigh into its loss;
    tokens_per_expert is a persistable [E] int32 statistic the scope holds
    after every step (it sums to tokens * top_k: no token is dropped),
    named `stat_name`_<n>: a program that shares a scope with the training
    program (an evaluation pass) gives its own so as not to overwrite the
    training step's."""
    if expert_act not in ("swiglu", "relu2"):
        raise ValueError("moe_ffn expert_act %r is neither swiglu nor relu2"
                         % (expert_act,))
    helper = LayerHelper("moe_ffn", **locals())
    dtype = helper.input_dtype()
    d = int(input.shape[-1])
    held = int(num_experts if num_local_experts is None
               else num_local_experts)
    router_w = helper.create_parameter(
        attr=router_attr, shape=[d, num_experts], dtype=dtype)
    inputs = {"X": [input], "RouterW": [router_w]}
    if expert_bias_attr is not None:
        if router != "sigmoid":
            raise ValueError("moe_ffn: an expert bias selects for the "
                             "sigmoid router only")
        expert_bias_attr.trainable = False
        inputs["ExpertBias"] = [helper.create_parameter(
            attr=expert_bias_attr, shape=[num_experts], dtype="float32")]
    inputs["GateUpW"] = [helper.create_parameter(
        attr=gate_up_attr,
        shape=[held, d, expert_size * (1 if expert_act == "relu2" else 2)],
        dtype=dtype)]
    inputs["DownW"] = [helper.create_parameter(
        attr=down_attr, shape=[held, expert_size, d], dtype=dtype)]
    counts = helper.create_global_variable(
        name=unique_name.generate(stat_name),
        persistable=True, dtype="int32", shape=[num_experts])
    counts.stop_gradient = True
    helper.set_variable_initializer(counts, Constant(0))
    out = helper.create_variable_for_type_inference(dtype)
    aux = helper.create_variable_for_type_inference("float32")
    # The output shapes are said here and the op is appended without the
    # abstract evaluation of its lowering that helper.append_op makes: with
    # an unknown batch that runs at a million sequences, whose rows times
    # top_k no int32 index reaches, and every layer's build would trace a
    # share's chunk loops at that size.
    attrs = {"top_k": int(top_k), "norm_topk_prob": bool(norm_topk_prob),
             "router": router, "expert_offset": int(expert_offset),
             "routed_scaling_factor": float(routed_scaling_factor),
             "norm_topk_eps": float(norm_topk_eps)}
    if expert_act != "swiglu":  # a SwiGLU op carries what it carried
        attrs["expert_act"] = expert_act
    helper.main_program.current_block().append_op(
        "moe_ffn", inputs,
        {"Y": [out], "TokensPerExpert": [counts], "AuxLoss": [aux]}, attrs)
    out.shape, aux.shape = tuple(input.shape), (2,)
    return out, aux, counts


def group_norm(
    input, groups, epsilon=1e-5, param_attr=None, bias_attr=None, act=None, name=None
):
    helper = LayerHelper("group_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1]
    inputs = {"X": [input]}
    if param_attr is not False:
        s = helper.create_parameter(
            attr=helper.param_attr,
            shape=[channels],
            dtype=dtype,
            default_initializer=Constant(1.0),
        )
        inputs["Scale"] = [s]
    if bias_attr is not False:
        b = helper.create_parameter(
            attr=helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
        )
        inputs["Bias"] = [b]
    out = helper.create_variable_for_type_inference(dtype)
    mean_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    var_out = helper.create_variable_for_type_inference(dtype, stop_gradient=True)
    helper.append_op(
        "group_norm",
        inputs=inputs,
        outputs={"Y": [out], "Mean": [mean_out], "Variance": [var_out]},
        attrs={"groups": groups, "epsilon": epsilon},
    )
    return helper.append_activation(out)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None, name=None):
    helper = LayerHelper("instance_norm", **locals())
    dtype = helper.input_dtype()
    channels = input.shape[1]
    inputs = {"X": [input]}
    s = helper.create_parameter(
        attr=helper.param_attr,
        shape=[channels],
        dtype=dtype,
        default_initializer=Constant(1.0),
    )
    b = helper.create_parameter(
        attr=helper.bias_attr, shape=[channels], dtype=dtype, is_bias=True
    )
    inputs["Scale"], inputs["Bias"] = [s], [b]
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "instance_norm", inputs=inputs, outputs={"Y": [out]}, attrs={"epsilon": epsilon}
    )
    return out


def dropout(
    x,
    dropout_prob,
    is_test=False,
    seed=None,
    name=None,
    dropout_implementation="downgrade_in_infer",
):
    helper = LayerHelper("dropout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    mask = helper.create_variable_for_type_inference(x.dtype, stop_gradient=True)
    helper.append_op(
        "dropout",
        inputs={"X": [x]},
        outputs={"Out": [out], "Mask": [mask]},
        attrs={
            "dropout_prob": dropout_prob,
            "is_test": is_test,
            "seed": seed if seed is not None else 0,
            "dropout_implementation": dropout_implementation,
        },
    )
    return out


def softmax(input, use_cudnn=True, name=None, axis=-1):
    return _simple("softmax", input, {"axis": axis}, name)


def log_softmax(input, axis=-1, name=None):
    return _simple("log_softmax", input, {"axis": axis}, name)


def cross_entropy(input, label, soft_label=False, ignore_index=-100):
    helper = LayerHelper("cross_entropy")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "cross_entropy",
        inputs={"X": [input], "Label": [label]},
        outputs={"Y": [out]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    return out


def softmax_with_cross_entropy(
    logits,
    label,
    soft_label=False,
    ignore_index=-100,
    numeric_stable_mode=True,
    return_softmax=False,
):
    helper = LayerHelper("softmax_with_cross_entropy")
    softmax_out = helper.create_variable_for_type_inference(logits.dtype)
    loss = helper.create_variable_for_type_inference(logits.dtype)
    helper.append_op(
        "softmax_with_cross_entropy",
        inputs={"Logits": [logits], "Label": [label]},
        outputs={"Softmax": [softmax_out], "Loss": [loss]},
        attrs={"soft_label": soft_label, "ignore_index": ignore_index},
    )
    if return_softmax:
        return loss, softmax_out
    return loss


def sigmoid_cross_entropy_with_logits(x, label, ignore_index=-100, name=None):
    helper = LayerHelper("sigmoid_cross_entropy_with_logits", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "sigmoid_cross_entropy_with_logits",
        inputs={"X": [x], "Label": [label]},
        outputs={"Out": [out]},
        attrs={"ignore_index": ignore_index},
    )
    return out


def square_error_cost(input, label):
    helper = LayerHelper("square_error_cost")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "square_error_cost",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out]},
    )
    return out


def smooth_l1(x, y, inside_weight=None, outside_weight=None, sigma=None):
    helper = LayerHelper("smooth_l1_loss")
    diff = helper.create_variable_for_type_inference(x.dtype)
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x], "Y": [y]}
    if inside_weight is not None:
        inputs["InsideWeight"] = [inside_weight]
    if outside_weight is not None:
        inputs["OutsideWeight"] = [outside_weight]
    helper.append_op(
        "smooth_l1_loss",
        inputs=inputs,
        outputs={"Out": [out], "Diff": [diff]},
        attrs={"sigma": sigma or 1.0},
    )
    return out


def huber_loss(input, label, delta):
    helper = LayerHelper("huber_loss")
    residual = helper.create_variable_for_type_inference(input.dtype)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "huber_loss",
        inputs={"X": [input], "Y": [label]},
        outputs={"Out": [out], "Residual": [residual]},
        attrs={"delta": delta},
    )
    return out


def label_smooth(label, prior_dist=None, epsilon=0.1, dtype="float32", name=None):
    helper = LayerHelper("label_smooth", name=name)
    out = helper.create_variable_for_type_inference(dtype)
    inputs = {"X": [label]}
    if prior_dist is not None:
        inputs["PriorDist"] = [prior_dist]
    helper.append_op(
        "label_smooth", inputs=inputs, outputs={"Out": [out]}, attrs={"epsilon": epsilon}
    )
    return out


def mean(x, name=None):
    return _simple("mean", x, name=name)


def mul(x, y, x_num_col_dims=1, y_num_col_dims=1, name=None):
    helper = LayerHelper("mul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "mul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"x_num_col_dims": x_num_col_dims, "y_num_col_dims": y_num_col_dims},
    )
    return out


def matmul(x, y, transpose_x=False, transpose_y=False, alpha=1.0, name=None):
    helper = LayerHelper("matmul", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "matmul",
        inputs={"X": [x], "Y": [y]},
        outputs={"Out": [out]},
        attrs={"transpose_X": transpose_x, "transpose_Y": transpose_y, "alpha": float(alpha)},
    )
    return out


def dot(x, y, name=None):
    helper = LayerHelper("dot", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op("dot", inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]})
    return out


def _reduce(op_type, input, dim, keep_dim, name):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    if dim is None:
        attrs = {"dim": [0], "keep_dim": keep_dim, "reduce_all": True}
    else:
        attrs = {
            "dim": dim if isinstance(dim, (list, tuple)) else [dim],
            "keep_dim": keep_dim,
            "reduce_all": False,
        }
    helper.append_op(op_type, inputs={"X": [input]}, outputs={"Out": [out]}, attrs=attrs)
    return out


def reduce_sum(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_sum", input, dim, keep_dim, name)


def reduce_mean(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_mean", input, dim, keep_dim, name)


def reduce_max(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_max", input, dim, keep_dim, name)


def reduce_min(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_min", input, dim, keep_dim, name)


def reduce_prod(input, dim=None, keep_dim=False, name=None):
    return _reduce("reduce_prod", input, dim, keep_dim, name)


def reshape(x, shape, actual_shape=None, act=None, inplace=False, name=None):
    helper = LayerHelper("reshape2", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "reshape2",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"shape": list(shape)},
    )
    return helper.append_activation(out) if act else out


def transpose(x, perm, name=None):
    return _simple("transpose2", x, {"axis": list(perm)}, name)


def flatten(x, axis=1, name=None):
    return _simple("flatten2", x, {"axis": axis}, name)


def squeeze(input, axes, name=None):
    return _simple("squeeze2", input, {"axes": list(axes)}, name)


def unsqueeze(input, axes, name=None):
    return _simple("unsqueeze2", input, {"axes": list(axes)}, name)


def split(input, num_or_sections, dim=-1, name=None):
    helper = LayerHelper("split", name=name)
    dim = dim if dim >= 0 else dim + len(input.shape)
    if isinstance(num_or_sections, int):
        num = num_or_sections
        sections = []
    else:
        num = 0
        sections = list(num_or_sections)
    outs = [
        helper.create_variable_for_type_inference(input.dtype)
        for _ in range(num or len(sections))
    ]
    helper.append_op(
        "split",
        inputs={"X": [input]},
        outputs={"Out": outs},
        attrs={"axis": dim, "num": num, "sections": sections},
    )
    return outs


def slice(input, axes, starts, ends):
    helper = LayerHelper("slice")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "slice",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={"axes": list(axes), "starts": list(starts), "ends": list(ends)},
    )
    return out


def expand(x, expand_times, name=None):
    return _simple("expand", x, {"expand_times": list(expand_times)}, name)


def stack(x, axis=0):
    helper = LayerHelper("stack")
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op(
        "stack", inputs={"X": x}, outputs={"Y": [out]}, attrs={"axis": axis}
    )
    return out


def unstack(x, axis=0, num=None):
    helper = LayerHelper("unstack")
    if num is None:
        num = x.shape[axis]
    outs = [helper.create_variable_for_type_inference(x.dtype) for _ in range(num)]
    helper.append_op(
        "unstack", inputs={"X": [x]}, outputs={"Y": outs}, attrs={"axis": axis}
    )
    return outs


def topk(input, k, name=None):
    helper = LayerHelper("top_k", name=name)
    values = helper.create_variable_for_type_inference(input.dtype)
    indices = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        "top_k",
        inputs={"X": [input]},
        outputs={"Out": [values], "Indices": [indices]},
        attrs={"k": k},
    )
    values.stop_gradient = True
    indices.stop_gradient = True
    return values, indices


def one_hot(input, depth):
    helper = LayerHelper("one_hot")
    out = helper.create_variable_for_type_inference("float32")
    helper.append_op(
        "one_hot", inputs={"X": [input]}, outputs={"Out": [out]}, attrs={"depth": depth}
    )
    return out


def l2_normalize(x, axis, epsilon=1e-12, name=None):
    helper = LayerHelper("l2_normalize", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    norm = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "l2_normalize",
        inputs={"X": [x]},
        outputs={"Out": [out], "Norm": [norm]},
        attrs={"axis": axis, "epsilon": epsilon},
    )
    return out


def clip(x, min, max, name=None):
    return _simple("clip", x, {"min": float(min), "max": float(max)}, name)


def clip_by_norm(x, max_norm, name=None):
    return _simple("clip_by_norm", x, {"max_norm": float(max_norm)}, name)


def _elementwise(op_type, x, y, axis=-1, act=None, name=None):
    helper = LayerHelper(op_type, name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        op_type, inputs={"X": [x], "Y": [y]}, outputs={"Out": [out]}, attrs={"axis": axis}
    )
    if act:
        helper.kwargs["act"] = act
        return helper.append_activation(out)
    return out


def elementwise_add(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_add", x, y, axis, act, name)


def elementwise_sub(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_sub", x, y, axis, act, name)


def elementwise_mul(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_mul", x, y, axis, act, name)


def elementwise_div(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_div", x, y, axis, act, name)


def elementwise_max(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_max", x, y, axis, act, name)


def elementwise_min(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_min", x, y, axis, act, name)


def elementwise_pow(x, y, axis=-1, act=None, name=None):
    return _elementwise("elementwise_pow", x, y, axis, act, name)


def pad(x, paddings, pad_value=0.0, name=None):
    return _simple("pad", x, {"paddings": list(paddings), "pad_value": pad_value}, name)


def pad2d(input, paddings=[0, 0, 0, 0], mode="constant", pad_value=0.0, data_format="NCHW", name=None):
    return _simple(
        "pad2d",
        input,
        {"paddings": list(paddings), "mode": mode, "pad_value": pad_value, "data_format": data_format},
        name,
    )


def prelu(x, mode, param_attr=None, name=None):
    helper = LayerHelper("prelu", **locals())
    alpha_shape = [1]
    if mode == "channel":
        alpha_shape = [x.shape[1]]
    elif mode == "element":
        alpha_shape = list(x.shape[1:])
    alpha = helper.create_parameter(
        attr=helper.param_attr,
        shape=alpha_shape,
        dtype="float32",
        default_initializer=Constant(0.25),
    )
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "prelu",
        inputs={"X": [x], "Alpha": [alpha]},
        outputs={"Out": [out]},
        attrs={"mode": mode},
    )
    return out


def maxout(x, groups, name=None):
    helper = LayerHelper("maxout", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "maxout", inputs={"X": [x]}, outputs={"Out": [out]}, attrs={"groups": groups}
    )
    return out


def relu(x, name=None):
    return _simple("relu", x, name=name)


def lrn(input, n=5, k=1.0, alpha=1e-4, beta=0.75, name=None):
    helper = LayerHelper("lrn", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    mid = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "lrn",
        inputs={"X": [input]},
        outputs={"Out": [out], "MidOut": [mid]},
        attrs={"n": n, "k": k, "alpha": alpha, "beta": beta},
    )
    return out


def image_resize(input, out_shape=None, scale=None, name=None, resample="BILINEAR"):
    op = "bilinear_interp" if resample.upper() == "BILINEAR" else "nearest_interp"
    if out_shape is None:
        out_shape = [int(input.shape[2] * scale), int(input.shape[3] * scale)]
    return _simple(op, input, {"out_h": out_shape[0], "out_w": out_shape[1]}, name)


def resize_bilinear(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, "BILINEAR")


def resize_nearest(input, out_shape=None, scale=None, name=None):
    return image_resize(input, out_shape, scale, name, "NEAREST")


def gather(input, index, overwrite=True):
    helper = LayerHelper("gather")
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "gather", inputs={"X": [input], "Index": [index]}, outputs={"Out": [out]}
    )
    return out


def gather_nd(input, index, name=None):
    helper = LayerHelper("gather_nd", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "gather_nd", inputs={"X": [input], "Index": [index]}, outputs={"Out": [out]}
    )
    return out


def scatter(input, index, updates, name=None, overwrite=True):
    helper = LayerHelper("scatter", name=name)
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "scatter",
        inputs={"X": [input], "Ids": [index], "Updates": [updates]},
        outputs={"Out": [out]},
        attrs={"overwrite": overwrite},
    )
    return out


def shape(input):
    helper = LayerHelper("shape")
    out = helper.create_variable_for_type_inference("int32")
    helper.append_op("shape", inputs={"Input": [input]}, outputs={"Out": [out]})
    return out


def gaussian_random(shape, mean=0.0, std=1.0, seed=0, dtype="float32"):
    helper = LayerHelper("gaussian_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "gaussian_random",
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "mean": mean, "std": std, "seed": seed, "dtype": dtype},
    )
    return out


def uniform_random(shape, dtype="float32", min=-1.0, max=1.0, seed=0):
    helper = LayerHelper("uniform_random")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "uniform_random",
        outputs={"Out": [out]},
        attrs={"shape": list(shape), "min": min, "max": max, "seed": seed, "dtype": dtype},
    )
    return out


def uniform_random_batch_size_like(
    input, shape, dtype="float32", input_dim_idx=0, output_dim_idx=0, min=-1.0, max=1.0, seed=0
):
    helper = LayerHelper("uniform_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "uniform_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={
            "shape": list(shape),
            "input_dim_idx": input_dim_idx,
            "output_dim_idx": output_dim_idx,
            "min": min,
            "max": max,
            "seed": seed,
            "dtype": dtype,
        },
    )
    return out


def gaussian_random_batch_size_like(
    input, shape, input_dim_idx=0, output_dim_idx=0, mean=0.0, std=1.0, seed=0, dtype="float32"
):
    helper = LayerHelper("gaussian_random_batch_size_like")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "gaussian_random_batch_size_like",
        inputs={"Input": [input]},
        outputs={"Out": [out]},
        attrs={
            "shape": list(shape),
            "input_dim_idx": input_dim_idx,
            "output_dim_idx": output_dim_idx,
            "mean": mean,
            "std": std,
            "seed": seed,
            "dtype": dtype,
        },
    )
    return out


def sum(x):
    helper = LayerHelper("sum")
    if isinstance(x, Variable):
        x = [x]
    out = helper.create_variable_for_type_inference(x[0].dtype)
    helper.append_op("sum", inputs={"X": x}, outputs={"Out": [out]})
    return out


def cos_sim(X, Y):
    helper = LayerHelper("cos_sim")
    xn = l2_normalize(X, axis=-1)
    yn = l2_normalize(Y, axis=-1)
    return reduce_sum(elementwise_mul(xn, yn), dim=-1, keep_dim=True)


def pow(x, factor=1.0, name=None):
    return _simple("pow", x, {"factor": float(factor)}, name)


def scale(x, scale=1.0, bias=0.0, bias_after_scale=True, act=None, name=None):
    helper = LayerHelper("scale", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "scale",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={
            "scale": float(scale),
            "bias": float(bias),
            "bias_after_scale": bias_after_scale,
        },
    )
    if act:
        helper.kwargs["act"] = act
        return helper.append_activation(out)
    return out


def hard_sigmoid(x, slope=0.2, offset=0.5, name=None):
    return _simple("hard_sigmoid", x, {"slope": slope, "offset": offset}, name)


def swish(x, beta=1.0, name=None):
    return _simple("swish", x, {"beta": beta}, name)


def leaky_relu(x, alpha=0.02, name=None):
    return _simple("leaky_relu", x, {"alpha": alpha}, name)


def elu(x, alpha=1.0, name=None):
    return _simple("elu", x, {"alpha": alpha}, name)


def relu6(x, threshold=6.0, name=None):
    return _simple("relu6", x, {"threshold": threshold}, name)


def pixel_shuffle(x, upscale_factor):
    return _simple("pixel_shuffle", x, {"upscale_factor": upscale_factor})


def where(condition, x=None, y=None):
    helper = LayerHelper("where")
    if x is None:
        out = helper.create_variable_for_type_inference("int64")
        helper.append_op(
            "where_index", inputs={"Condition": [condition]}, outputs={"Out": [out]}
        )
        return out
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "where",
        inputs={"Condition": [condition], "X": [x], "Y": [y]},
        outputs={"Out": [out]},
    )
    return out


def cond_take(x, mask):
    """Masked take with static shapes: values of ``x`` where ``mask`` is
    true, stably compacted to the front of a zero-padded full-size buffer,
    plus the true count (the TPU-shaped CondOp/masked-select)."""
    helper = LayerHelper("cond_take")
    out = helper.create_variable_for_type_inference(x.dtype)
    count = helper.create_variable_for_type_inference("int64")
    helper.append_op(
        "cond_take",
        inputs={"X": [x], "Mask": [mask]},
        outputs={"Out": [out], "Count": [count]},
    )
    return out, count


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col as a layer: NCHW -> [N, C*kh*kw, L] sliding patches
    (unfold_op; the host im2col of the reference's math/im2col.h becomes
    one fused XLA gather)."""
    def _pair(v):
        return [v, v] if isinstance(v, int) else [int(i) for i in v]

    helper = LayerHelper("unfold", name=name)
    out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "unfold",
        inputs={"X": [x]},
        outputs={"Y": [out]},
        attrs={
            "kernel_sizes": _pair(kernel_sizes),
            "strides": _pair(strides),
            "paddings": _pair(paddings),
            "dilations": _pair(dilations),
        },
    )
    return out


def increment(x, value=1.0, in_place=True):
    helper = LayerHelper("increment")
    if in_place:
        out = x
    else:
        out = helper.create_variable_for_type_inference(x.dtype)
    helper.append_op(
        "increment", inputs={"X": [x]}, outputs={"Out": [out]}, attrs={"step": float(value)}
    )
    return out


def cumsum(x, axis=-1, exclusive=False, reverse=False):
    return _simple(
        "cumsum", x, {"axis": axis, "exclusive": exclusive, "reverse": reverse}
    )


# ---------------------------------------------------------------------------
# recurrent layers (padded, scan-backed — nn.py dynamic_lstm/dynamic_gru
# re-expressed for static shapes; see ops/nn_ops.py padded_lstm)
# ---------------------------------------------------------------------------
def dynamic_lstm(
    input,
    size,
    h_0=None,
    c_0=None,
    param_attr=None,
    bias_attr=None,
    use_peepholes=False,
    is_reverse=False,
    gate_activation="sigmoid",
    cell_activation="tanh",
    candidate_activation="tanh",
    dtype="float32",
    name=None,
    seq_len=None,
):
    """LSTM over padded [batch, time, 4*hidden] input (projection done by a
    preceding fc, as in the reference's dynamic_lstm contract nn.py:443).
    Returns (hidden [B,T,H], cell-last [B,H])."""
    helper = LayerHelper("lstm", **locals())
    hidden_size = size // 4
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[hidden_size, 4 * hidden_size], dtype=dtype
    )
    b = helper.create_parameter(
        attr=helper.bias_attr, shape=[4 * hidden_size], dtype=dtype, is_bias=True
    )
    hidden = helper.create_variable_for_type_inference(dtype)
    last_h = helper.create_variable_for_type_inference(dtype)
    last_c = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input], "Weight": [w], "Bias": [b]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if c_0 is not None:
        inputs["C0"] = [c_0]
    if seq_len is not None:
        inputs["SeqLen"] = [seq_len]
    helper.append_op(
        "padded_lstm",
        inputs=inputs,
        outputs={"Hidden": [hidden], "LastH": [last_h], "LastC": [last_c]},
        attrs={"is_reverse": is_reverse},
    )
    return hidden, last_c


def lstm(input, size, **kwargs):
    return dynamic_lstm(input, size, **kwargs)


def dynamic_gru(
    input,
    size,
    param_attr=None,
    bias_attr=None,
    is_reverse=False,
    h_0=None,
    dtype="float32",
    name=None,
    seq_len=None,
):
    """GRU over padded [batch, time, 3*hidden] projected input."""
    helper = LayerHelper("gru", **locals())
    w = helper.create_parameter(
        attr=helper.param_attr, shape=[size, 3 * size], dtype=dtype
    )
    hidden = helper.create_variable_for_type_inference(dtype)
    last_h = helper.create_variable_for_type_inference(dtype)
    inputs = {"Input": [input], "Weight": [w]}
    if h_0 is not None:
        inputs["H0"] = [h_0]
    if seq_len is not None:
        inputs["SeqLen"] = [seq_len]
    helper.append_op(
        "padded_gru",
        inputs=inputs,
        outputs={"Hidden": [hidden], "LastH": [last_h]},
        attrs={"is_reverse": is_reverse},
    )
    return hidden


def gru(input, size, **kwargs):
    return dynamic_gru(input, size, **kwargs)


def beam_search(
    pre_ids,
    pre_scores,
    ids,
    scores,
    beam_size,
    end_id,
    level=0,
    is_accumulated=True,
    name=None,
    return_parent_idx=False,
):
    """One beam-search step (layers/nn.py:3174 analog, padded-batch form).

    Contract differs from the LoD reference: `scores` must be rank-3
    [batch, beam, vocab] next-token log-probs (already accumulated with the
    hypothesis history when is_accumulated=True, the default); `pre_ids` /
    `pre_scores` are [batch, beam].  Selects the top `beam_size`
    continuations over beam*vocab per batch row.
    Returns (selected_ids, selected_scores[, parent_idx]), each
    [batch, beam]."""
    helper = LayerHelper("beam_search", **locals())
    sel_ids = helper.create_variable_for_type_inference("int32")
    sel_scores = helper.create_variable_for_type_inference("float32")
    parent_idx = helper.create_variable_for_type_inference("int32")
    inputs = {"pre_ids": [pre_ids], "pre_scores": [pre_scores], "scores": [scores]}
    if ids is not None:
        inputs["ids"] = [ids]
    helper.append_op(
        "beam_search",
        inputs=inputs,
        outputs={
            "selected_ids": [sel_ids],
            "selected_scores": [sel_scores],
            "parent_idx": [parent_idx],
        },
        attrs={"beam_size": beam_size, "end_id": end_id, "level": level,
               "is_accumulated": is_accumulated},
    )
    if return_parent_idx:
        return sel_ids, sel_scores, parent_idx
    return sel_ids, sel_scores


def beam_search_decode(ids, scores, parent_idx=None, beam_size=None, end_id=0, name=None):
    """Backtrack per-step beam choices into full hypotheses
    (layers/nn.py beam_search_decode analog). `ids`/`scores`/`parent_idx`
    are stacked per-step tensors [T, batch, beam]."""
    helper = LayerHelper("beam_search_decode", **locals())
    sent_ids = helper.create_variable_for_type_inference("int32")
    sent_scores = helper.create_variable_for_type_inference("float32")
    inputs = {"Ids": [ids], "Scores": [scores]}
    if parent_idx is not None:
        inputs["ParentIdx"] = [parent_idx]
    helper.append_op(
        "beam_search_decode",
        inputs=inputs,
        outputs={"SentenceIds": [sent_ids], "SentenceScores": [sent_scores]},
        attrs={"end_id": end_id},
    )
    return sent_ids, sent_scores


def log_loss(input, label, epsilon=1e-4, name=None):
    """Negative-log-likelihood of a probability (log_loss_op.cc)."""
    helper = LayerHelper("log_loss", **locals())
    out = helper.create_variable_for_type_inference(input.dtype)
    helper.append_op(
        "log_loss",
        inputs={"Predicted": [input], "Labels": [label]},
        outputs={"Loss": [out]},
        attrs={"epsilon": epsilon},
    )
    return out


def fused_attention(q, k, v, causal=False, scale=None, bias=None,
                    window=0, segment_ids=None, qstart=None, name=None,
                    layout="bhtd"):
    """Fused scaled-dot-product attention over [batch, heads, T, d]
    (the blockwise flash kernel, or its one-tile form under 512 positions,
    where the placed platform and the shape choose it:
    ops/nn_ops._flash_engages, _short_engages).  layout="bthd": q, k, v
    are [batch, T, heads, d], a projection's [batch, T, heads * d]
    reshaped, and the result is laid out alike; the op computes the same
    thing, and on a TPU-placed step the one-tile form reads and writes
    those arrays in place where ops/nn_ops._in_place_engages says so (the
    transposes around a "bhtd" op cost two copies an operand there), every
    other path transposes inside the lowering.  V may be of another width
    than Q and K ([batch, heads, Tk, d_v]: latent attention scores 192
    wide over 128-wide values); the result is [batch, heads, Tq, d_v].
    bias: optional
    additive key-padding bias, rank-1 in the key axis ([B, Tk] or
    [B, 1, 1, Tk]) — covers padding masks without a [Tq, Tk] tensor;
    combine with causal=True for decoder self-attention.  window > 0
    (requires causal): sliding-window local attention — each query
    attends only the last `window` positions, and fully-out-of-window
    blocks are skipped in the flash kernels.  segment_ids: optional
    [B, T] int ids from sequence packing (reader.packing) — attention
    stays within each packed segment (ids compared on the fly, no
    [T, T] mask tensor; two extra rank-1 operands of the flash kernel
    where it is engaged, fused into the dense softmax otherwise).  qstart:
    optional [1] int var (chunked KV-cached decode): query i sits at
    GLOBAL position qstart + i while keys sit at their cache indices —
    causal masking applies in global positions and Tq may differ from
    Tk (requires causal=True).  A [batch] qstart keeps PER-ROW offsets
    (the continuous-batching ragged step: each serving slot gets its
    own causal cutoff inside one dispatch; dense-XLA path)."""
    window = int(window)
    if window < 0:
        raise ValueError("fused_attention: window must be >= 0")
    if window and not causal:
        raise ValueError("fused_attention: window requires causal=True")
    if qstart is not None and not causal:
        raise ValueError("fused_attention: qstart requires causal=True "
                         "(it defines the global causal cutoffs)")
    if layout not in ("bhtd", "bthd"):
        raise ValueError("fused_attention: layout is 'bhtd' or 'bthd', got "
                         "%r" % (layout,))
    helper = LayerHelper("fused_attention", **locals())
    out = helper.create_variable_for_type_inference(q.dtype)
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if bias is not None:
        inputs["Bias"] = [bias]
    if segment_ids is not None:
        inputs["SegmentIds"] = [segment_ids]
    if qstart is not None:
        inputs["QStart"] = [qstart]
    # Out is Q's shape at V's width, in Q's dtype, said here: appended
    # straight to the block, the op's lowering (a kernel, where platform
    # and shape choose one) is never evaluated to build a program
    helper.main_program.current_block().append_op(
        "fused_attention",
        inputs=inputs,
        outputs={"Out": [out]},
        attrs=dict({"causal": causal, "scale": scale, "window": int(window)},
                   **({"layout": layout} if layout != "bhtd" else {})),
    )
    out.shape = tuple(q.shape[:-1]) + (v.shape[-1],)
    return out


def slot_cache_write(cache, new, pos, width, name=None):
    """Per-row ragged KV-cache write (continuous-batching serving step):
    row b of `new` [B, H, W, D] lands in `cache` [B, H, T, D] at time
    indices pos[b]..pos[b]+width[b]-1; columns beyond width[b] (or past
    the cache) are dropped, never clamped.  Returns the updated
    full-length cache tensor (the caller assigns it back to the
    persistable var, as with seq_cache_write)."""
    helper = LayerHelper("slot_cache_write", **locals())
    out = helper.create_variable_for_type_inference(cache.dtype)
    helper.append_op(
        "slot_cache_write",
        inputs={"Cache": [cache], "New": [new], "Pos": [pos],
                "Width": [width]},
        outputs={"Out": [out]},
    )
    return out


# the keys of a published `rope_parameters` group that `rotary_embed`'s
# `scaling` reads; any other key, or rope_type, is refused, not guessed
_YARN_KEYS = {"rope_type", "factor", "original_max_position_embeddings",
              "beta_fast", "beta_slow", "attention_factor"}


def rotary_embed(x, pos=None, base=10000.0, interleaved=False, scaling=None,
                 name=None):
    """Rotary position embedding over per-head projections [B, H, T, Dh]
    (rotate-half).  pos: optional int positions [T] — the KV-cached
    decode path passes the current position so cached keys are stored
    pre-rotated; default arange(T).  A [B, T] pos keeps per-row
    positions (ragged serving step).  interleaved=True is for weights
    published in the (2i, 2i+1) pairing (DeepSeek-V3's `rope_interleave`):
    the last axis is first de-interleaved to (i, i + Dh/2) and then
    rotated as above, and the result stays in that order: the same
    permutation on q and k leaves every score as it was.  The pairing is
    undone by a product with a constant Dh x Dh permutation matrix, bit
    for bit what strided slices give: a float32 input is multiplied at
    precision HIGHEST (the default would round it to bfloat16 on a TPU's
    MXU), and a NaN or infinity in x reaches its whole row of Dh.

    scaling: a published `rope_parameters` group, {"rope_type": "yarn",
    "factor", "original_max_position_embeddings", "beta_fast", "beta_slow",
    "attention_factor"} (the last 0.1 ln(factor) + 1 where left out):
    YaRN's static inverse frequencies over Dh (`ops/nn_ops.
    _rotary_inv_freq`) and a factor on cos and sin.  The op then carries
    `yarn_*` and `attention_factor` attributes; without `scaling` it is
    the op every program has.  No `pos` with it: the cached decode paths
    have no scaled frequencies yet."""
    helper = LayerHelper("rotary_embed", **locals())
    out = helper.create_variable_for_type_inference(x.dtype)
    inputs = {"X": [x]}
    if pos is not None:
        inputs["Pos"] = [pos]
    attrs = {"base": base}
    if interleaved:  # the default leaves the op as every program has it
        attrs["interleaved"] = True
    if scaling is not None:
        if scaling.get("rope_type") != "yarn":
            raise NotImplementedError(
                "rotary_embed: rope_type %r; scaled frequencies are yarn's"
                % (scaling.get("rope_type"),))
        if set(scaling) - _YARN_KEYS:
            raise NotImplementedError(
                "rotary_embed: yarn scaling has no %s"
                % sorted(set(scaling) - _YARN_KEYS))
        if pos is not None:
            raise NotImplementedError(
                "rotary_embed: scaled frequencies are the training path's; "
                "the cached decode paths (pos) have none yet")
        factor = float(scaling["factor"])
        amplitude = scaling.get("attention_factor")
        attrs.update(
            yarn_factor=factor,
            yarn_original_max_position=float(
                scaling["original_max_position_embeddings"]),
            yarn_beta_fast=float(scaling.get("beta_fast", 32)),
            yarn_beta_slow=float(scaling.get("beta_slow", 1)),
            attention_factor=float(0.1 * math.log(factor) + 1.0
                                   if amplitude is None else amplitude))
    helper.append_op("rotary_embed", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def conv3d_transpose(
    input,
    num_filters,
    output_size=None,
    filter_size=None,
    padding=0,
    stride=1,
    dilation=1,
    groups=None,
    param_attr=None,
    bias_attr=None,
    use_cudnn=True,
    act=None,
    name=None,
):
    """conv3d_transpose (nn.py conv3d_transpose parity): NCDHW transposed
    convolution (ops/nn_ops.py _conv3d_transpose)."""
    helper = LayerHelper("conv3d_transpose", **locals())
    dtype = helper.input_dtype()
    num_channels = input.shape[1]
    groups = groups or 1

    def _trip(v):
        return [v] * 3 if isinstance(v, int) else list(v)

    stride, padding, dilation = map(_trip, (stride, padding, dilation))
    if filter_size is None:
        if output_size is None:
            raise ValueError(
                "conv3d_transpose needs filter_size or output_size"
            )
        output_size = _trip(output_size)
        # invert out = (in-1)*s - 2p + d*(k-1) + 1 per spatial dim
        filter_size = [
            (output_size[i] - (input.shape[2 + i] - 1) * stride[i]
             + 2 * padding[i] - 1) // dilation[i] + 1
            for i in range(3)
        ]
    else:
        filter_size = _trip(filter_size)
    filter_shape = [num_channels, num_filters // groups] + filter_size
    w = helper.create_parameter(attr=helper.param_attr, shape=filter_shape, dtype=dtype)
    pre_bias = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "conv3d_transpose",
        inputs={"Input": [input], "Filter": [w]},
        outputs={"Output": [pre_bias]},
        attrs={
            "strides": stride,
            "paddings": padding,
            "dilations": dilation,
            "groups": groups,
        },
    )
    pre_act = helper.append_bias_op(pre_bias, dim_start=1, dim_end=2)
    return helper.append_activation(pre_act)


def sampling_id(x, min=0.0, max=1.0, seed=0, dtype="int32"):
    """Sample a category per row of a probability matrix
    (nn.py sampling_id / sampling_id_op.cc)."""
    helper = LayerHelper("sampling_id")
    out = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "sampling_id",
        inputs={"X": [x]},
        outputs={"Out": [out]},
        attrs={"seed": seed},
    )
    return out


def data_norm(
    input,
    act=None,
    epsilon=1e-05,
    param_attr=None,
    data_layout="NCHW",
    in_place=False,
    name=None,
    moving_mean_name=None,
    moving_variance_name=None,
    do_model_average_for_mean_and_var=False,
):
    """Batch-statistics normalization for CTR models (nn.py data_norm /
    data_norm_op.cc): accumulators are persistable state the op updates
    each step."""
    helper = LayerHelper("data_norm", **locals())
    dtype = helper.input_dtype()
    d = input.shape[-1]
    attr = param_attr or ParamAttr()
    from ..initializer import Constant

    bsz = helper.create_global_variable(
        name=unique_name.generate("data_norm_batch_size"),
        persistable=True, dtype=dtype, shape=[d],
    )
    bsum = helper.create_global_variable(
        name=unique_name.generate("data_norm_batch_sum"),
        persistable=True, dtype=dtype, shape=[d],
    )
    bsq = helper.create_global_variable(
        name=unique_name.generate("data_norm_batch_square_sum"),
        persistable=True, dtype=dtype, shape=[d],
    )
    helper.set_variable_initializer(bsz, Constant(1e4))
    helper.set_variable_initializer(bsum, Constant(0.0))
    helper.set_variable_initializer(bsq, Constant(1e4))
    out = helper.create_variable_for_type_inference(dtype)
    means = helper.create_variable_for_type_inference(dtype)
    scales = helper.create_variable_for_type_inference(dtype)
    helper.append_op(
        "data_norm",
        inputs={"X": [input], "BatchSize": [bsz], "BatchSum": [bsum],
                "BatchSquareSum": [bsq]},
        outputs={"Y": [out], "Means": [means], "Scales": [scales],
                 "BatchSizeOut": [bsz], "BatchSumOut": [bsum],
                 "BatchSquareSumOut": [bsq]},
        attrs={"epsilon": epsilon},
    )
    return helper.append_activation(out)
