"""Math / elementwise / reduction / activation op lowerings.

TPU-native re-expression of the reference's ``paddle/fluid/operators/``
elementwise_*, activation, reduce_ops, matmul/mul, softmax and loss ops: each
is one pure JAX rule that XLA fuses into neighboring ops (replacing the
hand-fused mkldnn/cudnn kernels and ``math/`` functor library).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.registry import register
from .common import bcast_y, jdt
from .kernel_tuning import note_dense_vjp
from .spmd_epilogue import mesh_ctx, rule_sharded_weight


# ---------------------------------------------------------------------------
# elementwise binary ops (operators/elementwise/*)
# ---------------------------------------------------------------------------
def _elementwise(fn):
    def lower(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        yb = bcast_y(x, y, attrs.get("axis", -1))
        out = fn(x, yb)
        scale = attrs.get("scale", None)
        if scale is not None and scale != 1.0:
            out = out * scale
        return {"Out": [out]}

    return lower


for name, fn in [
    ("elementwise_add", jnp.add),
    ("elementwise_sub", jnp.subtract),
    ("elementwise_mul", jnp.multiply),
    ("elementwise_div", jnp.divide),
    ("elementwise_max", jnp.maximum),
    ("elementwise_min", jnp.minimum),
    ("elementwise_pow", jnp.power),
    ("elementwise_mod", jnp.mod),
    ("elementwise_floordiv", jnp.floor_divide),
]:
    register(name)(_elementwise(fn))


# ---------------------------------------------------------------------------
# comparison / logical (operators/controlflow/compare_op.cc, logical_op.cc)
# ---------------------------------------------------------------------------
def _compare(fn):
    def lower(ctx, ins, attrs):
        x, y = ins["X"][0], ins["Y"][0]
        return {"Out": [fn(x, bcast_y(x, y, attrs.get("axis", -1)))]}

    return lower


for name, fn in [
    ("less_than", jnp.less),
    ("less_equal", jnp.less_equal),
    ("greater_than", jnp.greater),
    ("greater_equal", jnp.greater_equal),
    ("equal", jnp.equal),
    ("not_equal", jnp.not_equal),
]:
    register(name, no_grad_inputs=("X", "Y"))(_compare(fn))


@register("logical_and", no_grad_inputs=("X", "Y"))
def _logical_and(ctx, ins, attrs):
    return {"Out": [jnp.logical_and(ins["X"][0], ins["Y"][0])]}


@register("logical_or", no_grad_inputs=("X", "Y"))
def _logical_or(ctx, ins, attrs):
    return {"Out": [jnp.logical_or(ins["X"][0], ins["Y"][0])]}


@register("logical_not", no_grad_inputs=("X",))
def _logical_not(ctx, ins, attrs):
    return {"Out": [jnp.logical_not(ins["X"][0])]}


@register("logical_xor", no_grad_inputs=("X", "Y"))
def _logical_xor(ctx, ins, attrs):
    return {"Out": [jnp.logical_xor(ins["X"][0], ins["Y"][0])]}


# ---------------------------------------------------------------------------
# activations (operators/activation_op.*)
# ---------------------------------------------------------------------------
def _act(fn):
    def lower(ctx, ins, attrs):
        return {"Out": [fn(ins["X"][0], attrs)]}

    return lower


_ACTS = {
    "relu": lambda x, a: jnp.maximum(x, 0),
    "sigmoid": lambda x, a: jax.nn.sigmoid(x),
    "tanh": lambda x, a: jnp.tanh(x),
    "sqrt": lambda x, a: jnp.sqrt(x),
    "rsqrt": lambda x, a: jax.lax.rsqrt(x),
    "abs": lambda x, a: jnp.abs(x),
    "ceil": lambda x, a: jnp.ceil(x),
    "floor": lambda x, a: jnp.floor(x),
    "round": lambda x, a: jnp.round(x),
    "cos": lambda x, a: jnp.cos(x),
    "sin": lambda x, a: jnp.sin(x),
    "exp": lambda x, a: jnp.exp(x),
    "log": lambda x, a: jnp.log(x),
    "square": lambda x, a: jnp.square(x),
    "reciprocal": lambda x, a: 1.0 / x,
    "softplus": lambda x, a: jax.nn.softplus(x),
    "softsign": lambda x, a: x / (1 + jnp.abs(x)),
    "relu6": lambda x, a: jnp.clip(x, 0, a.get("threshold", 6.0)),
    "leaky_relu": lambda x, a: jnp.where(x > 0, x, a.get("alpha", 0.02) * x),
    "elu": lambda x, a: jnp.where(x > 0, x, a.get("alpha", 1.0) * (jnp.exp(x) - 1)),
    "gelu": lambda x, a: jax.nn.gelu(x, approximate=a.get("approximate", False)),
    "hard_sigmoid": lambda x, a: jnp.clip(
        a.get("slope", 0.2) * x + a.get("offset", 0.5), 0, 1
    ),
    "swish": lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x),
    "brelu": lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)),
    "soft_relu": lambda x, a: jnp.log(
        1 + jnp.exp(jnp.clip(x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))
    ),
    "stanh": lambda x, a: a.get("scale_b", 1.7159) * jnp.tanh(a.get("scale_a", 0.67) * x),
    "thresholded_relu": lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0.0),
    "hard_shrink": lambda x, a: jnp.where(jnp.abs(x) > a.get("threshold", 0.5), x, 0.0),
    "tanh_shrink": lambda x, a: x - jnp.tanh(x),
    "logsigmoid": lambda x, a: jax.nn.log_sigmoid(x),
    "sign": lambda x, a: jnp.sign(x),
    "erf": lambda x, a: jax.lax.erf(x),
}
for name, fn in _ACTS.items():
    register(name)(_act(fn))


@register("prelu")
def _prelu(ctx, ins, attrs):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": [jnp.where(x > 0, x, alpha * x)]}


@register("pow")
def _pow(ctx, ins, attrs):
    return {"Out": [jnp.power(ins["X"][0], attrs.get("factor", 1.0))]}


@register("scale", handles_selected_rows=True)
def _scale(ctx, ins, attrs):
    from ..core.selected_rows import SelectedRows

    x = ins["X"][0]
    s = attrs.get("scale", 1.0)
    b = attrs.get("bias", 0.0)
    if isinstance(x, SelectedRows):
        if b:  # a bias densifies by definition
            x = x.densify()
        else:
            return {"Out": [x.scaled(s)]}
    if attrs.get("bias_after_scale", True):
        return {"Out": [x * s + b]}
    return {"Out": [(x + b) * s]}


@register("clip")
def _clip(ctx, ins, attrs):
    return {"Out": [jnp.clip(ins["X"][0], attrs["min"], attrs["max"])]}


@register("clip_by_norm")
def _clip_by_norm(ctx, ins, attrs):
    x = ins["X"][0]
    max_norm = attrs["max_norm"]
    norm = jnp.sqrt(jnp.sum(jnp.square(x)))
    return {"Out": [jnp.where(norm > max_norm, x * (max_norm / norm), x)]}


@register("isfinite", no_grad_inputs=("X",))
def _isfinite(ctx, ins, attrs):
    # reference isfinite reduces over all inputs to a single bool
    ok = jnp.array(True)
    for x in ins["X"]:
        ok = jnp.logical_and(ok, jnp.all(jnp.isfinite(x)))
    return {"Out": [ok]}


@register("has_inf", no_grad_inputs=("X",))
def _has_inf(ctx, ins, attrs):
    """isfinite_op.cc OverflowOp family: any(isinf) over all inputs."""
    bad = jnp.array(False)
    for x in ins["X"]:
        bad = jnp.logical_or(bad, jnp.any(jnp.isinf(x)))
    return {"Out": [bad]}


@register("has_nan", no_grad_inputs=("X",))
def _has_nan(ctx, ins, attrs):
    bad = jnp.array(False)
    for x in ins["X"]:
        bad = jnp.logical_or(bad, jnp.any(jnp.isnan(x)))
    return {"Out": [bad]}


# ---------------------------------------------------------------------------
# matmul family (operators/mul_op.cc, matmul_op.cc)
# ---------------------------------------------------------------------------
def _flatten2(x, ncol):
    lead = 1
    for d in x.shape[:ncol]:
        lead *= d
    rest = 1
    for d in x.shape[ncol:]:
        rest *= d
    return x.reshape(lead, rest)


@register("mul")
def _mul(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    xn = attrs.get("x_num_col_dims", 1)
    yn = attrs.get("y_num_col_dims", 1)
    x2 = _flatten2(x, xn)
    y2 = _flatten2(y, yn)
    out = x2 @ y2
    out_shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    return {"Out": [out.reshape(out_shape)]}


@register("matmul")
def _matmul(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    tx, ty = attrs.get("transpose_X", False), attrs.get("transpose_Y", False)
    alpha = attrs.get("alpha", 1.0)
    if x.ndim == 1:
        x = x[None, :] if not tx else x[:, None]
    if y.ndim == 1:
        y = y[:, None] if not ty else y[None, :]
    if tx:
        x = jnp.swapaxes(x, -1, -2)
    if ty:
        y = jnp.swapaxes(y, -1, -2)
    out = jnp.matmul(x, y)
    if alpha != 1.0:
        out = out * alpha
    return {"Out": [out]}


@register("dot")
def _dot(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.sum(x * y, axis=-1, keepdims=True)]}


# ---------------------------------------------------------------------------
# reductions (operators/reduce_ops/*)
# ---------------------------------------------------------------------------
def _reduce(fn):
    def lower(ctx, ins, attrs):
        x = ins["X"][0]
        if attrs.get("reduce_all", False):
            axis = None
        else:
            dim = attrs.get("dim", [0])
            axis = tuple(d % x.ndim for d in (dim if isinstance(dim, (list, tuple)) else [dim]))
        out = fn(x, axis=axis, keepdims=attrs.get("keep_dim", False))
        return {"Out": [out]}

    return lower


for name, fn in [
    ("reduce_sum", jnp.sum),
    ("reduce_mean", jnp.mean),
    ("reduce_max", jnp.max),
    ("reduce_min", jnp.min),
    ("reduce_prod", jnp.prod),
]:
    register(name)(_reduce(fn))


@register("mean")
def _mean(ctx, ins, attrs):
    return {"Out": [jnp.mean(ins["X"][0]).reshape(1)]}


@register("sum", handles_selected_rows=True)
def _sum_op(ctx, ins, attrs):
    from ..core.selected_rows import SelectedRows, densify_maybe

    xs = ins["X"]
    if xs and all(isinstance(x, SelectedRows) for x in xs):
        # grad fan-in of sparse grads stays sparse: concatenate the row
        # sets (duplicates are fine — consumers merge or scatter-add)
        rows = jnp.concatenate([x.rows for x in xs])
        vals = jnp.concatenate([x.value for x in xs])
        return {"Out": [SelectedRows(rows, vals, xs[0].height)]}
    xs = [densify_maybe(x) for x in xs]
    out = xs[0]
    for x in xs[1:]:
        out = out + x
    return {"Out": [out]}


@register("squared_l2_norm")
def _squared_l2_norm(ctx, ins, attrs):
    return {"Out": [jnp.sum(jnp.square(ins["X"][0])).reshape(1)]}


@register("frobenius_norm")
def _frobenius_norm(ctx, ins, attrs):
    return {"Out": [jnp.sqrt(jnp.sum(jnp.square(ins["X"][0]))).reshape(1)]}


@register("norm")
def _norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


# ---------------------------------------------------------------------------
# softmax & losses (operators/softmax_op, cross_entropy_op,
# softmax_with_cross_entropy_op)
# ---------------------------------------------------------------------------
@register("softmax")
def _softmax(ctx, ins, attrs):
    axis = attrs.get("axis", -1)
    return {"Out": [jax.nn.softmax(ins["X"][0], axis=axis)]}


@register("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return {"Out": [jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1))]}


def _take_label(x, label):
    """x[..., label] along last axis; label shape [..., 1] int."""
    lbl = label.astype(jnp.int32)
    if lbl.ndim == x.ndim:
        lbl = lbl[..., 0]
    return jnp.take_along_axis(x, lbl[..., None], axis=-1)


@register("cross_entropy", no_grad_inputs=("Label",))
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(jnp.clip(x, 1e-20, None)), axis=-1, keepdims=True)
    else:
        p = _take_label(x, label)
        loss = -jnp.log(jnp.clip(p, 1e-20, None))
    return {"Y": [loss]}


@register("softmax_with_cross_entropy", no_grad_inputs=("Label",))
def _softmax_xent(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    logp = jax.nn.log_softmax(logits, axis=-1)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=-1, keepdims=True)
    else:
        lp = _take_label(logp, label)
        if attrs.get("ignore_index", -100) >= 0:
            ig = attrs["ignore_index"]
            lbl = label if label.ndim == logits.ndim else label[..., None]
            mask = (lbl.astype(jnp.int32) != ig).astype(logp.dtype)
            lp = lp * mask
        loss = -lp
    return {"Softmax": [jnp.exp(logp)], "Loss": [loss]}


@register("smooth_label_xent", no_grad_inputs=("Label",))
def _smooth_label_xent(ctx, ins, attrs):
    """Label-smoothed softmax cross-entropy in closed form — the fused
    target of smooth_label_xent_fuse_pass (one_hot -> label_smooth ->
    softmax_with_cross_entropy(soft_label), the reference training-loss
    idiom: label_smooth_op.cc + softmax_with_cross_entropy_op.cc).

    With s = (1-eps)*onehot(y) + eps/V (uniform prior) and
    logp = logits - lse:

        -sum(s * logp) = (1-eps)*(lse - logits[y]) + eps*(lse - mean(logits))

    so NO [N, V] one-hot / smoothed-label / log-softmax array is ever
    materialized in HBM — at transformer-base bench config that is three
    ~1.3 GB f32 arrays per step direction.  f32 internals regardless of
    the (possibly bf16) logits dtype; grads via the generic vjp."""
    logits = ins["Logits"][0]
    label = ins["Label"][0]
    eps = float(attrs.get("epsilon", 0.0))
    lg = logits.astype(jnp.float32)
    v = lg.shape[-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    ly = _take_label(lg, label)
    # out-of-range labels (e.g. -1 padding ids): one_hot emitted an
    # all-zero row there, so the unfused loss is just the smoothing term
    # — match it exactly instead of take_along_axis's wrap/clamp gather
    lbl = label.astype(jnp.int32)
    if lbl.ndim == lg.ndim:
        lbl = lbl[..., 0]
    valid = ((lbl >= 0) & (lbl < v))[..., None]
    smooth_term = (
        eps * (lse - jnp.mean(lg, axis=-1, keepdims=True)) if eps
        else jnp.zeros_like(lse)
    )
    loss = jnp.where(valid, (1.0 - eps) * (lse - ly), 0.0) + smooth_term
    return {"Loss": [loss.astype(logits.dtype)]}


def _linear_xent_dense(x2d, w, labels, eps=0.0):
    """The reference linear_xent_tiled is tested against: the [R, V]
    logits under jax's autodiff.  Same label convention as
    smooth_label_xent: out-of-range labels contribute the smoothing
    term only."""
    lg = jnp.dot(x2d, w, preferred_element_type=jnp.float32)
    v = lg.shape[-1]
    lse = jax.scipy.special.logsumexp(lg, axis=-1, keepdims=True)
    lbl = labels.astype(jnp.int32).reshape(-1)
    onehot_gold = jnp.sum(
        jnp.where(jnp.arange(v)[None, :] == lbl[:, None], lg, 0.0),
        axis=-1, keepdims=True)
    valid = ((lbl >= 0) & (lbl < v))[:, None]
    loss = jnp.where(valid, (1.0 - eps) * (lse - onehot_gold), 0.0)
    if eps:
        loss = loss + eps * (lse - jnp.mean(lg, axis=-1, keepdims=True))
    return loss


# ---------------------------------------------------------------------------
# the vocabulary head: _linear_xent_dense's arithmetic as a custom VJP in
# plain XLA ops that walks the rows in tiles, so no [R, V] array exists
# and the logits gradient is formed once, narrowed to the operands' dtype
# and fed to both gradient matmuls
# ---------------------------------------------------------------------------
# the f32 [rows_t, V] logits block one tile may hold: 1024 rows at
# V=50257, 4096 at V=10000
_LXENT_TILE_BYTES = 256 << 20


def _lxent_tile_len(batch, length, vocab):
    """Steps of the scanned axis a tile takes, from the shapes alone: the
    largest divisor of `length` (a multiple of 8 when tiling at all)
    whose f32 [batch * steps, vocab] block fits _LXENT_TILE_BYTES and is
    over half of what fits; without one, what fits rounded down to 8 and
    a padded last tile."""
    cap = max(1, _LXENT_TILE_BYTES // (4 * vocab * batch))
    if cap >= length:
        return length
    align = 8 if cap >= 8 else 1
    top = cap - cap % align
    for steps in range(top, cap // 2, -align):
        if length % steps == 0:
            return steps
    return top


def _lxent_split(a, steps, fill):
    """[B, T, ...] -> [n, B, steps, ...] tiles of axis 1, the last one
    padded with `fill`."""
    b, t = a.shape[:2]
    n = -(-t // steps)
    if n * steps != t:
        pad = [(0, 0), (0, n * steps - t)] + [(0, 0)] * (a.ndim - 2)
        a = jnp.pad(a, pad, constant_values=fill)
    return jnp.moveaxis(a.reshape((b, n, steps) + a.shape[2:]), 1, 0)


def _lxent_join(tiles, length):
    """Inverse of _lxent_split: [n, B, steps, ...] -> [B, length, ...]."""
    a = jnp.moveaxis(tiles, 0, 1)
    a = a.reshape((a.shape[0], -1) + a.shape[3:])
    return a[:, :length]


def _lxent_scan(tile, init, tiles):
    """lax.scan of `tile` over the leading axis of `tiles`; a single tile
    is called in line, so a partitioned program holds no loop at all."""
    if jax.tree_util.tree_leaves(tiles)[0].shape[0] > 1:
        return jax.lax.scan(tile, init, tiles)
    carry, out = tile(init, jax.tree_util.tree_map(lambda a: a[0], tiles))
    return carry, out[None]


def _lxent_as_tiles(x, w, labels, transpose_w, one_tile):
    """(x tiles [n, B, steps, H], label tiles [n, B, steps], w in the
    dots' dtype, V, (B, T)).  A [R, H] input tiles its rows; with a time
    axis ([..., T, H]) that axis is the scanned one, so an axis the mesh
    shards over dp (batch) stays whole in every tile."""
    dt = jnp.result_type(x.dtype, w.dtype)
    h = x.shape[-1]
    t = x.shape[-2]
    x3 = x.reshape(-1, t, h).astype(dt)
    lbl = labels.astype(jnp.int32).reshape(x3.shape[:2])
    vocab = w.shape[0 if transpose_w else 1]
    steps = t if one_tile else _lxent_tile_len(x3.shape[0], t, vocab)
    return (_lxent_split(x3, steps, 0), _lxent_split(lbl, steps, -1),
            w.astype(dt), vocab, x3.shape[:2])


def _lxent_tile_logits(x_t, w, transpose_w):
    """f32 [B, steps, V] logits of one tile; the tied [V, H] table
    contracts its second axis, never a transposed copy."""
    return jax.lax.dot_general(
        x_t, w, (((2,), (1 if transpose_w else 0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _lxent_tiled_fwd(x, w, labels, eps, transpose_w, one_tile):
    note_dense_vjp("xent")
    xs, ls, wd, vocab, (_, t) = _lxent_as_tiles(x, w, labels, transpose_w,
                                                one_tile)

    def tile(_, x_l):
        x_t, l_t = x_l
        z = _lxent_tile_logits(x_t, wd, transpose_w)
        lse = jax.scipy.special.logsumexp(z, axis=-1)
        cols = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
        gold = jnp.sum(jnp.where(cols == l_t[..., None], z, 0.0), axis=-1)
        valid = (l_t >= 0) & (l_t < vocab)
        loss = jnp.where(valid, (1.0 - eps) * (lse - gold), 0.0)
        if eps:
            loss = loss + eps * (lse - jnp.mean(z, axis=-1))
        # ONE output: the forward op reads the loss, the grad op's
        # re-traced forward reads lse; as two outputs each side's dead
        # code elimination would prune a different one, the two scans
        # would differ and XLA's CSE could not merge them into one
        return None, jnp.stack([loss, lse], axis=-1)

    with jax.named_scope("tile_fwd"):
        _, stats = _lxent_scan(tile, None, (xs, ls))
    stats = _lxent_join(stats, t)
    loss = stats[..., 0].reshape(x.shape[:-1] + (1,))
    return loss, stats[..., 1]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def linear_xent_tiled(x, w, labels, eps=0.0, transpose_w=False,
                      one_tile=False):
    """Projected cross entropy in plain XLA ops with a hand-written VJP:
    _linear_xent_dense's arithmetic (operands in their own dtype to the
    MXU, f32 accumulation, f32 softmax statistics; out-of-range labels
    contribute the smoothing term only) without an [R, V] array.  x
    [..., H], w [H, V] ([V, H] with transpose_w), labels x.shape[:-1]
    int; returns x.shape[:-1] + (1,) f32 losses.

    Forward: a scan over row tiles (_lxent_as_tiles), per tile the f32
    logits, reduced to the loss and lse; only lse is saved beside x, w
    and the labels.  Backward: per tile the logits again, the logits
    gradient formed ONCE in f32, narrowed to the operands' dtype, and
    that one array fed to both gradient matmuls; dw accumulates in f32
    across the tiles.

    one_tile: the whole input as a single tile and no loop — for a
    program GSPMD partitions, where the dw carried through a scan would
    be all-reduced over dp once per tile instead of once."""
    return _lxent_tiled_fwd(x, w, labels, eps, transpose_w, one_tile)[0]


def _lxent_tiled_vjp_fwd(x, w, labels, eps, transpose_w, one_tile):
    loss, lse = _lxent_tiled_fwd(x, w, labels, eps, transpose_w, one_tile)
    return loss, (x, w, labels, lse)


def _lxent_tiled_vjp_bwd(eps, transpose_w, one_tile, res, dy):
    x, w, labels, lse = res
    xs, ls, wd, vocab, (b, t) = _lxent_as_tiles(x, w, labels, transpose_w,
                                                one_tile)
    steps = xs.shape[2]
    # a padded row takes dy = 0, so it reaches neither dx nor dw
    dys = _lxent_split(dy.astype(jnp.float32).reshape(b, t), steps, 0)
    lses = _lxent_split(lse, steps, 0)

    def tile(dw, tile_in):
        x_t, l_t, lse_t, dy_t = tile_in
        z = _lxent_tile_logits(x_t, wd, transpose_w)
        p = jnp.exp(z - lse_t[..., None])
        cols = jax.lax.broadcasted_iota(jnp.int32, z.shape, 2)
        onehot = (cols == l_t[..., None]).astype(jnp.float32)
        valid = ((l_t >= 0) & (l_t < vocab)).astype(jnp.float32)
        g = ((1.0 - eps) * valid)[..., None] * (p - onehot)
        if eps:
            g = g + eps * (p - 1.0 / vocab)
        g = (g * dy_t[..., None]).astype(wd.dtype)
        dx_t = jax.lax.dot_general(
            g, wd, (((2,), (0 if transpose_w else 1,)), ((), ())),
            preferred_element_type=jnp.float32)
        lhs, rhs = (g, x_t) if transpose_w else (x_t, g)
        dw = dw + jax.lax.dot_general(
            lhs, rhs, (((0, 1), (0, 1)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw, dx_t.astype(x.dtype)

    with jax.named_scope("tile_bwd"):
        dw, dxs = _lxent_scan(tile, jnp.zeros(w.shape, jnp.float32),
                              (xs, ls, lses, dys))
    dx = _lxent_join(dxs, t).reshape(x.shape)
    dlbl = np.zeros(labels.shape, dtype=jax.dtypes.float0)
    return dx, dw.astype(w.dtype), dlbl


linear_xent_tiled.defvjp(_lxent_tiled_vjp_fwd, _lxent_tiled_vjp_bwd)


@register("fused_linear_xent", no_grad_inputs=("Label",))
def _fused_linear_xent_op(ctx, ins, attrs):
    """Projected cross entropy — the fused target of
    linear_xent_fuse_pass (the final [H, V] projection folded INTO
    softmax_with_cross_entropy / smooth_label_xent).  Inputs: X
    [..., H] hidden states, W [H, V] (or [V, H] with transpose_w, the
    tied-embedding form), Label [..., 1] int.  Label convention matches
    smooth_label_xent: out-of-range labels contribute the smoothing
    term only.

    The lowering is linear_xent_tiled, a custom VJP in plain XLA ops
    that walks the rows in tiles of at most ~256 MiB of f32 logits, so
    no [R, V] array exists in either direction; the backward recomputes
    a tile's logits from the saved lse, forms the logits gradient once,
    narrows it to the operands' dtype and feeds both gradient matmuls.
    transpose_w is the dots' dimension numbers, not a copy of the table.
    Under a live GSPMD mesh (spmd_epilogue.mesh_ctx) the input is one
    tile and there is no loop: the partitioner would all-reduce a scan's
    dw carry over dp once per tile.  GSPMD then splits that tile by W's
    sharding: the stored one where mp divides the vocabulary; where it
    does not, W arrives replicated and a training step constrains it
    (forward and the grad op's re-traced forward alike) to the rule's
    uneven spec (spmd_epilogue.rule_sharded_weight), so a rank holds
    logits for its share of the columns, all-reduces two row statistics
    over mp and reduces its rows of dw over dp; a serving table leaves W
    whole.  The engagement counts under
    kernel_tuning.attribution()["dense_vjp_hits"]["xent"], a placed
    constraint under ["uneven_constraints"]["fused_linear_xent"]."""
    x = ins["X"][0]
    w = rule_sharded_weight(ctx, ("fused_linear_xent",), "W", ins["W"][0])
    label = ins["Label"][0]
    eps = float(attrs.get("epsilon", 0.0))
    transpose_w = bool(attrs.get("transpose_w", False))
    loss = linear_xent_tiled(x, w, label.reshape(x.shape[:-1]), eps,
                             transpose_w, mesh_ctx() is not None)
    # the per-row loss leaves in f32 whatever X's dtype, like every
    # other softmax statistic: under AMP (bf16 X) a cast to X's dtype
    # rounds each row's loss to 8 bits before the cast-back op AMP
    # appends.  XLA elided that rounding while the whole head was one
    # fusion (xla_allow_excess_precision); across a loop boundary it
    # keeps it, 3e-4..3e-3 on a mean loss of 7 (PERF.md, PR 24)
    loss = loss.reshape(tuple(x.shape[:-1]) + (1,)).astype(jnp.float32)
    return {"Loss": [loss]}


@register("sigmoid_cross_entropy_with_logits", no_grad_inputs=("Label",))
def _sigmoid_xent(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    mask = (label != ignore).astype(x.dtype)
    return {"Out": [loss * mask]}


@register("square_error_cost", no_grad_inputs=("Y",))
def _square_error(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return {"Out": [jnp.square(x - y)]}


@register("smooth_l1_loss", no_grad_inputs=("Y", "InsideWeight", "OutsideWeight"))
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    s2 = sigma * sigma
    diff = x - y
    if ins.get("InsideWeight"):
        diff = diff * ins["InsideWeight"][0]
    ad = jnp.abs(diff)
    loss = jnp.where(ad < 1.0 / s2, 0.5 * s2 * ad * ad, ad - 0.5 / s2)
    if ins.get("OutsideWeight"):
        loss = loss * ins["OutsideWeight"][0]
    loss = jnp.sum(loss.reshape(loss.shape[0], -1), axis=1, keepdims=True)
    return {"Out": [loss], "Diff": [diff]}


@register("huber_loss", no_grad_inputs=("Y",))
def _huber(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [loss], "Residual": [r]}


@register("label_smooth", no_grad_inputs=("PriorDist",))
def _label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.0)
    prior = ins.get("PriorDist", [None])[0]
    if prior is None:
        prior = 1.0 / x.shape[-1]
    return {"Out": [(1 - eps) * x + eps * prior]}


# ---------------------------------------------------------------------------
# metrics (operators/metrics/*)
# ---------------------------------------------------------------------------
@register("top_k", no_grad_inputs=("X",))
def _top_k(ctx, ins, attrs):
    x = ins["X"][0]
    k = attrs["k"]
    vals, idx = jax.lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int32)]}


@register("accuracy", no_grad_inputs=("Out", "Indices", "Label"))
def _accuracy(ctx, ins, attrs):
    idx = ins["Indices"][0]
    label = ins["Label"][0]
    if label.ndim < idx.ndim:
        label = label[..., None]
    correct = jnp.any(idx == label.astype(idx.dtype), axis=-1)
    total = correct.shape[0]
    num_correct = jnp.sum(correct.astype(jnp.int32))
    acc = num_correct.astype(jnp.float32) / total
    return {
        "Accuracy": [acc.reshape(1)],
        "Correct": [num_correct.reshape(1)],
        "Total": [jnp.array([total], jnp.int32)],
    }


@register("arg_max", no_grad_inputs=("X",))
def _arg_max(ctx, ins, attrs):
    return {"Out": [jnp.argmax(ins["X"][0], axis=attrs.get("axis", -1)).astype(jnp.int32)]}


@register("arg_min", no_grad_inputs=("X",))
def _arg_min(ctx, ins, attrs):
    return {"Out": [jnp.argmin(ins["X"][0], axis=attrs.get("axis", -1)).astype(jnp.int32)]}


@register("argsort", no_grad_inputs=("X",))
def _argsort(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    idx = jnp.argsort(x, axis=axis)
    return {"Out": [jnp.sort(x, axis=axis)], "Indices": [idx.astype(jnp.int32)]}


@register("cumsum")
def _cumsum(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    if attrs.get("flatten", False):
        x = x.reshape(-1)
        axis = 0
    out = jnp.cumsum(x, axis=axis)
    if attrs.get("reverse", False):
        out = jnp.flip(jnp.cumsum(jnp.flip(x, axis), axis=axis), axis)
    if attrs.get("exclusive", False):
        out = out - x
    return {"Out": [out]}


@register("maximum")
def _maximum(ctx, ins, attrs):
    return {"Out": [jnp.maximum(ins["X"][0], ins["Y"][0])]}


@register("minimum")
def _minimum(ctx, ins, attrs):
    return {"Out": [jnp.minimum(ins["X"][0], ins["Y"][0])]}


# ---------------------------------------------------------------------------
# static infer rules (analysis/infer.py): registered alongside the
# lowerings so the shape/dtype contract and the kernel live in one file
# ---------------------------------------------------------------------------
from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    elementwise_shape,
    register_infer,
    same_as,
    same_dtype,
    slot_info as _i,
)


def _ew_infer(op, ins):
    x, y = _i(ins, "X"), _i(ins, "Y")
    shape = elementwise_shape(x, y, op.attrs.get("axis", -1))
    return {"Out": [VarInfo(shape, same_dtype(x, y))]}


for _name in (
    "elementwise_add", "elementwise_sub", "elementwise_mul",
    "elementwise_div", "elementwise_max", "elementwise_min",
    "elementwise_pow", "elementwise_mod", "elementwise_floordiv",
    "maximum", "minimum",
):
    register_infer(_name, req_ins=("X", "Y"))(_ew_infer)


def _cmp_infer(op, ins):
    x, y = _i(ins, "X"), _i(ins, "Y")
    shape = elementwise_shape(x, y, op.attrs.get("axis", -1))
    return {"Out": [VarInfo(shape, "bool")]}


for _name in (
    "less_than", "less_equal", "greater_than", "greater_equal",
    "equal", "not_equal", "logical_and", "logical_or", "logical_xor",
):
    register_infer(_name, req_ins=("X", "Y"))(_cmp_infer)
register_infer("logical_not", req_ins=("X",))(
    lambda op, ins: {"Out": [VarInfo(
        _i(ins, "X").shape if _i(ins, "X") else None, "bool")]})

for _name in tuple(_ACTS) + (
    "pow", "clip", "clip_by_norm", "softmax", "log_softmax", "cumsum",
):
    register_infer(_name, req_ins=("X",))(same_as("X"))
register_infer("scale", req_ins=("X",))(same_as("X"))
register_infer("prelu", req_ins=("X", "Alpha"))(same_as("X"))


def _reduce_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {"Out": [VarInfo(None, x.dtype if x else None)]}
    nd = len(x.shape)
    if op.attrs.get("reduce_all", False):
        axes = set(range(nd))
    else:
        dim = op.attrs.get("dim", [0])
        dim = dim if isinstance(dim, (list, tuple)) else [dim]
        axes = set(int(d) % nd for d in dim)
    keep = bool(op.attrs.get("keep_dim", False))
    shape = tuple(
        1 if (i in axes and keep) else d
        for i, d in enumerate(x.shape)
        if keep or i not in axes)
    return {"Out": [VarInfo(shape, x.dtype)]}


for _name in ("reduce_sum", "reduce_mean", "reduce_max", "reduce_min",
              "reduce_prod"):
    register_infer(_name, req_ins=("X",))(_reduce_infer)


@register_infer("mean", req_ins=("X",))
def _mean_infer(op, ins):
    x = _i(ins, "X")
    return {"Out": [VarInfo((1,), x.dtype if x else None)]}


@register_infer("sum", req_ins=("X",))
def _sum_infer(op, ins):
    x = _i(ins, "X")
    if x is None:
        return {}
    return {"Out": [VarInfo(x.shape, x.dtype)]}


def _mm_flat(shape, k):
    lead, tail = shape[:k], shape[k:]
    from ..analysis.infer import numel_known

    return numel_known(lead), numel_known(tail)


@register_infer("mul", req_ins=("X", "Y"))
def _mul_infer(op, ins):
    x, y = _i(ins, "X"), _i(ins, "Y")
    if x is None or y is None or x.shape is None or y.shape is None:
        return {"Out": [VarInfo(None, same_dtype(x, y))]}
    xn = int(op.attrs.get("x_num_col_dims", 1))
    yn = int(op.attrs.get("y_num_col_dims", 1))
    if not (0 < xn < len(x.shape) + 1 and 0 < yn < len(y.shape) + 1):
        raise InferError(
            "mul num_col_dims (%d, %d) out of range for ranks (%d, %d)"
            % (xn, yn, len(x.shape), len(y.shape)))
    _, xk = _mm_flat(x.shape, xn)
    yk, _ = _mm_flat(y.shape, yn)
    if xk is not None and yk is not None and xk != yk:
        raise InferError(
            "mul contraction mismatch: X%s flattens to K=%d but Y%s "
            "expects K=%d" % (x.shape, xk, y.shape, yk))
    shape = tuple(x.shape[:xn]) + tuple(y.shape[yn:])
    return {"Out": [VarInfo(shape, same_dtype(x, y))]}


@register_infer("matmul", req_ins=("X", "Y"))
def _matmul_infer(op, ins):
    from ..analysis.infer import broadcast_shapes

    x, y = _i(ins, "X"), _i(ins, "Y")
    if x is None or y is None or x.shape is None or y.shape is None:
        return {"Out": [VarInfo(None, same_dtype(x, y))]}
    xs, ys = list(x.shape), list(y.shape)
    tx = bool(op.attrs.get("transpose_X", False))
    ty = bool(op.attrs.get("transpose_Y", False))
    if len(xs) == 1:
        xs = [1, xs[0]] if not tx else [xs[0], 1]
    if len(ys) == 1:
        ys = [ys[0], 1] if not ty else [1, ys[0]]
    if tx:
        xs[-1], xs[-2] = xs[-2], xs[-1]
    if ty:
        ys[-1], ys[-2] = ys[-2], ys[-1]
    if xs[-1] >= 0 and ys[-2] >= 0 and xs[-1] != ys[-2]:
        raise InferError(
            "matmul contraction mismatch: %s @ %s (transpose_X=%s, "
            "transpose_Y=%s)" % (x.shape, y.shape, tx, ty))
    batch = broadcast_shapes(xs[:-2], ys[:-2], "matmul batch")
    shape = None if batch is None else tuple(batch) + (xs[-2], ys[-1])
    return {"Out": [VarInfo(shape, same_dtype(x, y))]}


@register_infer("dot", req_ins=("X", "Y"))
def _dot_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    return {"Out": [VarInfo(x.shape[:-1] + (1,), x.dtype)]}


def _rowloss_shape(x):
    if x is None or x.shape is None:
        return None
    return x.shape[:-1] + (1,)


@register_infer("cross_entropy", req_ins=("X", "Label"), req_outs=("Y",))
def _xent_infer(op, ins):
    x = _i(ins, "X")
    return {"Y": [VarInfo(_rowloss_shape(x), x.dtype if x else None)]}


@register_infer("softmax_with_cross_entropy", req_ins=("Logits", "Label"),
                req_outs=("Loss",))
def _sxent_infer(op, ins):
    x = _i(ins, "Logits")
    return {
        "Softmax": [VarInfo(x.shape if x else None, x.dtype if x else None)],
        "Loss": [VarInfo(_rowloss_shape(x), x.dtype if x else None)],
    }


@register_infer("smooth_label_xent", req_ins=("Logits", "Label"),
                req_outs=("Loss",))
def _slx_infer(op, ins):
    x = _i(ins, "Logits")
    return {"Loss": [VarInfo(_rowloss_shape(x), x.dtype if x else None)]}


@register_infer("fused_linear_xent", req_ins=("X", "W", "Label"),
                req_outs=("Loss",))
def _flx_infer(op, ins):
    x, w = _i(ins, "X"), _i(ins, "W")
    if x is None or x.shape is None:
        return {}
    if (w is not None and w.shape is not None and len(w.shape) == 2
            and x.shape[-1] >= 0):
        h = w.shape[1] if op.attrs.get("transpose_w", False) else w.shape[0]
        if h >= 0 and x.shape[-1] != h:
            raise InferError(
                "fused_linear_xent hidden-dim mismatch: X%s vs W%s "
                "(transpose_w=%s)" % (x.shape, w.shape,
                                      bool(op.attrs.get("transpose_w"))))
    return {"Loss": [VarInfo(_rowloss_shape(x), x.dtype)]}


@register_infer("square_error_cost", req_ins=("X", "Y"))
def _sec_infer(op, ins):
    x = _i(ins, "X")
    return {"Out": [VarInfo(x.shape if x else None, x.dtype if x else None)]}


@register_infer("top_k", req_ins=("X",), req_outs=("Out", "Indices"))
def _topk_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    k = int(op.attrs.get("k", 1))
    shape = x.shape[:-1] + (k,)
    return {"Out": [VarInfo(shape, x.dtype)],
            "Indices": [VarInfo(shape, None)]}


@register_infer("accuracy", req_ins=("Indices", "Label"),
                req_outs=("Accuracy",))
def _acc_infer(op, ins):
    return {"Accuracy": [VarInfo((1,), "float32")]}


def _arg_infer(op, ins):
    x = _i(ins, "X")
    if x is None or x.shape is None:
        return {}
    nd = len(x.shape)
    ax = int(op.attrs.get("axis", -1)) % nd
    keep = bool(op.attrs.get("keepdims", False))
    shape = tuple(
        1 if (i == ax and keep) else d
        for i, d in enumerate(x.shape) if keep or i != ax)
    return {"Out": [VarInfo(shape, None)]}


register_infer("arg_max", req_ins=("X",))(_arg_infer)
register_infer("arg_min", req_ins=("X",))(_arg_infer)
