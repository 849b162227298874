"""Routed experts as a Program op: `moe_ffn`.

A token-choice mixture of experts (SwiGLU, or the ungated
down(relu(up x)^2) under `expert_act` "relu2"; softmax or sigmoid router,
top-k, no capacity: dropless under any imbalance) lowered with static
shapes: the N*k (token, expert) assignments are sorted by expert, the
tokens gathered into one [N*k, d] array, and the experts run as two
grouped matmuls over its contiguous groups, whose sizes are data.  An
expert that receives no token is a group of size zero.  Nothing here is a
[tokens, experts, capacity] tensor (`parallel/moe.py`'s dispatch, which no
op lowers to).

A chip's share of an expert layer: the op holds the experts
[expert_offset, expert_offset + E_held) (E_held the leading dimension of
its expert weights) of the E its router chooses among, routes over all E
and computes its own experts' part of the result.  Assignments to experts
held elsewhere sort behind the held ones, outside every group, so the
grouped matmuls' work follows the live rows (the kernels' grids stop at
the last live tile), and so does the row work around them: the gather of
the tokens' rows, the SwiGLU, the weighting and their transposes run over
the chunks of C rows that hold a live row, ceil(live / C) trips of a loop
whose body is traced once, and never touch the rest.  C comes from the
shape alone: the largest multiple of the kernels' 256-row tile up to
`_CHUNK_ROWS` that divides N*k, the whole buffer where there is none.  An
op that holds every expert has nothing to skip and keeps the whole-size
lowering.  An expert width the kernels' 128-lane tile does not divide is
padded with zero columns inside the op where the kernels would otherwise
engage (`_kernel_widths`).  What the experts held elsewhere would add is left out, forward
and backward.  Nothing stands in for the other chips or their exchange.

The lowering opens `route`, `dispatch`, `experts` and `combine` under the
op's own `<role>/moe_ffn/<index>` scope, so a device trace splits the op's
time the way `tile_fwd` / `tile_bwd` split the vocabulary head's.
"""

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register
from . import kernel_tuning


# megablox tiles, from a sweep alone on a v5e at the OLMoE shape (65,536
# bf16 rows in 64 uneven groups; 2048x2048 and 1024x2048; PERF.md, PR 25):
# 256 rows (a tile that straddles a group boundary is computed twice, so
# 512 rows waste more), the whole contraction up to 2048 in one tile and
# as many columns as a 2048 x 1024 weight tile allows.  The weights'
# gradient (tgmm) holds an f32 [tk, tn] accumulator and fits 1024 x 1024.
# Larger tiles exceed VMEM ("Ran out of memory in memory space vmem").
_GMM_ROWS = 256
_GMM_MAX_CONTRACTION = 2048
_GMM_WEIGHT_TILE = 2048 * 1024
_TGMM_MAX = 1024


def _tile(width, cap):
    """The widest tile up to `cap` that Mosaic takes and `width` divides
    into: a multiple of 128 that divides it (LFM2's 1792 and 3584 are
    14 and 28 x 128), else the width or the cap themselves."""
    fits = [t for t in range(128, min(width, cap) + 1, 128)
            if width % t == 0]
    return fits[-1] if fits else min(width, cap)


def _gmm_tile(k, n):
    tk = _tile(k, _GMM_MAX_CONTRACTION)
    return (_GMM_ROWS, tk, _tile(n, _GMM_WEIGHT_TILE // tk))


def _megablox_fits(ctx, lhs, rhs):
    """The Pallas grouped matmul is for a step placed on the chip (it
    would be interpreted elsewhere: LowerCtx.platform, which the Executor
    states; a caller that did not say gets `ragged_dot`), for a single
    device (XLA cannot partition a Mosaic call under a GSPMD mesh), and
    for rows and widths its tiles divide."""
    from .spmd_epilogue import mesh_ctx

    return (ctx.platform == "tpu" and mesh_ctx() is None
            and lhs.shape[0] % _GMM_ROWS == 0
            and rhs.shape[1] % 128 == 0 and rhs.shape[2] % 128 == 0)


@jax.custom_vjp
def _megablox_gmm(lhs, rhs, group_sizes):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(lhs, rhs, group_sizes, lhs.dtype,
               _gmm_tile(rhs.shape[1], rhs.shape[2]))


def _mgmm_fwd(lhs, rhs, group_sizes):
    return _megablox_gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _mgmm_bwd(res, g):
    """megablox's own VJP (ops.gmm) with a tile per direction: the rows'
    gradient contracts over N, the weights' over the rows."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    k, n = rhs.shape[1], rhs.shape[2]
    d_lhs = gmm(g, rhs, group_sizes, lhs.dtype, _gmm_tile(n, k),
                transpose_rhs=True)
    d_rhs = tgmm(lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
                 (_GMM_ROWS, _tile(k, _TGMM_MAX), _tile(n, _TGMM_MAX)),
                 num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


_megablox_gmm.defvjp(_mgmm_fwd, _mgmm_bwd)


def _product(kernel, lhs, rhs, group_sizes):
    if kernel:
        return _megablox_gmm(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


def _product_grads(kernel, lhs, rhs, group_sizes, g):
    """(d lhs, d rhs) of _product: the kernels' transposes, or what jax's
    autodiff makes of ragged_dot.  The two leave together: the scheduler
    would otherwise put every layer's weight gradient off to the end of
    the backward and hold its two N*k-row operands until then."""
    if kernel:
        grads = _mgmm_bwd((lhs, rhs, group_sizes), g)[:2]
    else:
        grads = jax.vjp(
            lambda lhs, rhs: _product(False, lhs, rhs, group_sizes),
            lhs, rhs)[1](g)
    return jax.lax.optimization_barrier(grads)


def _kernel_widths(ctx, rows, w_gu, w_down, halves):
    """The experts' weights as the kernels take them: where the Pallas
    grouped matmul would engage but for an expert width that is no multiple
    of its 128-lane tile (Nemotron-H's 1856 = 14.5 x 128), the width padded
    with zero columns of the first weight (of each of its `halves`: gate
    and up) and zero rows of the second, which add nothing to any result
    or gradient (the body of a zero column is zero); `ragged_dot`, the
    other lowering, computes every group over the whole row buffer on a
    TPU (PERF.md section 6, PR 57).  A width the tile divides, and every
    placement where the kernel does not engage anyway, passes through."""
    f = w_down.shape[1]
    pad = -f % 128
    lhs = jax.ShapeDtypeStruct((rows, w_gu.shape[1]), w_gu.dtype)
    if not pad or not _megablox_fits(
            ctx, lhs, jax.ShapeDtypeStruct(
                (w_gu.shape[0], w_gu.shape[1], 128), w_gu.dtype)):
        return w_gu, w_down
    e, d = w_gu.shape[:2]
    w_gu = jnp.pad(w_gu.reshape(e, d, halves, f),
                   ((0, 0), (0, 0), (0, 0), (0, pad))).reshape(
                       e, d, halves * (f + pad))
    return w_gu, jnp.pad(w_down, ((0, 0), (0, pad), (0, 0)))


def _takes_kernel(ctx, lhs, rhs):
    """_megablox_fits, counted at trace time by what it answered."""
    fits = _megablox_fits(ctx, lhs, rhs)
    if fits:
        kernel_tuning.note_kernel("grouped_matmul")
    else:
        kernel_tuning.note_dense_vjp("grouped_matmul")
    return fits


def grouped_matmul(ctx, lhs, rhs, group_sizes):
    """[M, K] x [G, K, N] -> [M, N]: rows of `lhs` in G contiguous groups
    of `group_sizes` rows, group g multiplied by rhs[g]; f32 accumulation,
    result in lhs's dtype.  Rows behind the last group belong to none:
    the kernel leaves them unwritten, `ragged_dot` zero, and the caller
    reads neither.  On the chip, megablox's Pallas `gmm` (and `gmm` over
    rhs^T / `tgmm` for the two gradients); elsewhere `jax.lax.ragged_dot`,
    whose transposes jax's autodiff supplies."""
    return _product(_takes_kernel(ctx, lhs, rhs), lhs, rhs, group_sizes)


def _sum_slots(rows, inv, k):
    """rows [N*k, d] in sorted order -> [N, d]: each token's k rows
    brought back to assignment order and summed in f32."""
    n = rows.shape[0] // k
    return rows[inv].reshape(n, k, -1).astype(jnp.float32).sum(1).astype(
        rows.dtype)


# The permutation to expert order and back is a pair of gathers that are
# each other's transpose (`tok` repeats every token k times, `inv` is the
# inverse of the sort).  Autodiff would transpose a gather into a
# scatter-add of N*k rows, which the TPU serialises; saying the transpose
# here keeps both directions gathers.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_expert_order(x, tok, inv, k):
    return x[tok]


def _teo_fwd(x, tok, inv, k):
    return x[tok], (tok, inv)


def _teo_bwd(k, res, g):
    tok, inv = res
    return _from_expert_order(g, tok, inv, k), None, None


_to_expert_order.defvjp(_teo_fwd, _teo_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _from_expert_order(rows, tok, inv, k):
    return _sum_slots(rows, inv, k)


def _feo_fwd(rows, tok, inv, k):
    return _sum_slots(rows, inv, k), (tok, inv)


def _feo_bwd(k, res, g):
    tok, inv = res
    return _to_expert_order(g, tok, inv, k), None, None


_from_expert_order.defvjp(_feo_fwd, _feo_bwd)



def _whole(ctx, x2, router_w, bias, w_gu, w_down, *, k, sigmoid, offset,
           norm, eps, scaling, act="swiglu"):
    """(Y [N, d], tokens per expert [E], aux [2]) of x2 [N, d] where the op
    holds every expert: all N*k rows are live."""
    (top_p, aux), (top_e, counts) = _routed(x2, router_w, bias, k, sigmoid,
                                            norm, eps, scaling)
    cdt = w_gu.dtype
    with jax.named_scope("dispatch"):
        order, inv, group_sizes = _expert_order(top_e, counts, offset,
                                                w_gu.shape[0])
        tok = order // k
        rows = _to_expert_order(x2.astype(cdt), tok, inv, k)
        row_p = _to_expert_order(top_p.reshape(-1, 1), order, inv, 1)
    with jax.named_scope("experts"):
        gu = grouped_matmul(ctx, rows, w_gu, group_sizes)
        out = grouped_matmul(ctx, _EXPERT_ACTS[act](gu), w_down, group_sizes)
    with jax.named_scope("combine"):
        out = (out.astype(jnp.float32) * row_p).astype(cdt)
        return _from_expert_order(out, tok, inv, k), counts, aux


# ---------------------------------------------------------------------------
# a chip's share: the row work over the live chunks
# ---------------------------------------------------------------------------
# Where the op holds a share, the n_live rows of the held experts come
# first in expert order and everything behind them belongs to no group.
# The kernels' grids stop at the last live tile by themselves; the XLA
# ops around them do not, so they run as loops over static chunks of
# `chunk` rows whose trip count, ceil(n_live / chunk), is data.  Such a
# loop has no reverse-mode rule, so the layer states its transpose
# (_share_bwd), in which each loop's is a loop of the same kind or a
# gather.  A buffer a loop fills starts as zeros (a memset); a buffer a
# kernel fills holds whatever memory held behind the live rows, and whoever
# reads it masks.  The loops are functions of their own inside the layer's
# two (jitted too: a device trace names them, and a test counts them).

# Rows of a chunk: the largest multiple of the kernels' row tile up to
# this that divides N*k (a sweep of both share cells on a v5e: PERF.md,
# PR 39); one chunk, the whole buffer, where none does.
_CHUNK_ROWS = 2048


def _chunk_rows(m):
    fits = [c for c in range(_GMM_ROWS, min(m, _CHUNK_ROWS) + 1, _GMM_ROWS)
            if m % c == 0]
    return fits[-1] if fits else m


def _rows(buf, start, chunk):
    return jax.lax.dynamic_slice_in_dim(buf, start, chunk, 0)


def _put(buf, rows, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, rows, start, 0)


def _live_chunks(n_live, chunk, carry, body):
    """`carry` after body(start, live, carry) over the chunks of `chunk`
    rows that hold a live row, first to last; `live` [chunk, 1] says which
    rows of the chunk at `start` are (all but in the last one)."""
    def step(c, carry):
        start = c * chunk
        live = (start + jnp.arange(chunk, dtype=jnp.int32) < n_live)[:, None]
        return body(start, live, carry)

    return jax.lax.fori_loop(0, (n_live + chunk - 1) // chunk, step, carry)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _gather_live(x, idx, n_live, *, chunk):
    """out[p] = x[idx[p]] for the live rows p < n_live, zero behind."""
    def body(start, live, out):
        got = x[_rows(idx, start, chunk)]
        return _put(out, jnp.where(live, got, 0), start)

    return _live_chunks(
        n_live, chunk, jnp.zeros((idx.shape[0], x.shape[1]), x.dtype), body)


def _live_slots(rows, inv, n_live, k, weigh=None):
    """rows [N*k, d] in expert order -> [N, d] f32: each token's live rows
    summed, a slot at a time (each [N, d] gather feeds the sum's fusion;
    [N, k, d] would be laid out anew first).  The gathers read the dead
    rows they are pointed at, which hold whatever a kernel left: the mask
    comes after them.  A scatter-add of the live rows is what the TPU
    serialises."""
    slots = inv.reshape(-1, k)
    total = 0.0
    for j in range(k):
        got = rows[slots[:, j]].astype(jnp.float32)
        if weigh is not None:
            got = got * weigh[:, j, None]
        total = total + jnp.where(slots[:, j, None] < n_live, got, 0)
    return total


@functools.partial(jax.jit, static_argnames=("k",))
def _sum_live_slots(rows, inv, n_live, *, k):
    return _live_slots(rows, inv, n_live, k).astype(rows.dtype)


def _swiglu(gu):
    f = gu.shape[1] // 2
    return (jax.nn.silu(gu[:, :f].astype(jnp.float32))
            * gu[:, f:].astype(jnp.float32)).astype(gu.dtype)


def _relu2(up):
    """relu(up)^2 in f32: the ungated expert's body (Nemotron-H's
    `mlp_hidden_act` relu2), up [rows, f] -> [rows, f]."""
    return jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(up.dtype)


# what stands between an expert's two matmuls, by the op's `expert_act`:
# the first weight is [E_held, d, 2f] (gate | up) under swiglu, [E_held, d,
# f] under relu2
_EXPERT_ACTS = {"swiglu": _swiglu, "relu2": _relu2}


@functools.partial(jax.jit, static_argnames=("chunk", "act"))
def _swiglu_live(gu, n_live, *, chunk, act="swiglu"):
    """The experts' body (silu(gate) * up of gu [N*k, 2f], or `act`'s) in
    f32 over the live rows."""
    body_of = _EXPERT_ACTS[act]

    def body(start, live, out):
        return _put(out, jnp.where(live, body_of(_rows(gu, start, chunk)), 0),
                    start)

    width = gu.shape[1] // 2 if act == "swiglu" else gu.shape[1]
    return _live_chunks(
        n_live, chunk, jnp.zeros((gu.shape[0], width), gu.dtype), body)


@functools.partial(jax.jit, static_argnames=("chunk", "act"))
def _swiglu_live_bwd(gu, g, n_live, *, chunk, act="swiglu"):
    def body(start, live, d_gu):
        _, vjp = jax.vjp(_EXPERT_ACTS[act], _rows(gu, start, chunk))
        (d,) = vjp(_rows(g, start, chunk))
        return _put(d_gu, jnp.where(live, d, 0), start)

    return _live_chunks(n_live, chunk, jnp.zeros_like(gu), body)


@functools.partial(jax.jit, static_argnames=("k",))
def _weigh_to_tokens(out, top_p, inv, n_live, *, k):
    """Y[t] = sum over token t's live slots of p * out[its row], in f32:
    the weighting rides in the sum's fusion, on the tokens' side."""
    return _live_slots(out, inv, n_live, k, top_p).astype(out.dtype)


@functools.partial(jax.jit, static_argnames=("k", "chunk"))
def _weigh_to_tokens_bwd(out, top_p, order, inv, g, n_live, *, k, chunk):
    p_flat = top_p.reshape(-1)

    def body(start, live, carry):
        d_out, d_p = carry
        at = _rows(order, start, chunk)
        g_rows = g[at // k].astype(jnp.float32)
        d_o = jnp.where(live, g_rows * p_flat[at][:, None], 0)
        d_pc = jnp.where(
            live[:, 0],
            (_rows(out, start, chunk).astype(jnp.float32) * g_rows).sum(-1),
            0)
        return (_put(d_out, d_o.astype(out.dtype), start),
                _put(d_p, d_pc, start))

    d_out, d_p = _live_chunks(
        n_live, chunk,
        (jnp.zeros_like(out), jnp.zeros(order.shape, jnp.float32)), body)
    return d_out, d_p[inv].reshape(top_p.shape)


def _routed(x2, router_w, bias, k, sigmoid, norm, eps, scaling):
    """((top-k weights [N, k], aux [2]), (their experts [N, k], tokens per
    expert [E])) under the op's `route` scope: the part of the layer that
    autodiff transposes."""
    with jax.named_scope("route"):
        if sigmoid:
            top_p, top_e, counts, aux = route_sigmoid(
                x2, router_w, bias, k, norm, eps)
        else:
            top_p, top_e, counts, aux = route(x2, router_w, k, norm)
        if scaling != 1.0:
            top_p = top_p * scaling
    return (top_p, aux), (top_e, counts)


def _expert_order(top_e, counts, offset, held):
    """The stable sort of the N*k assignments by expert (`inv` undoes it)
    and the groups' sizes; where the op holds a share, the held experts'
    rows first, by local expert, and the assignments to experts held
    elsewhere behind them, in no group."""
    sort_key, group_sizes = top_e.reshape(-1), counts
    if held != counts.shape[0]:
        local = sort_key - offset
        sort_key = jnp.where((local >= 0) & (local < held), local, held)
        group_sizes = counts[offset:offset + held]
    order = jnp.argsort(sort_key, stable=True)
    return order, jnp.argsort(order), group_sizes


# A share's layer whole, forward and backward, as two functions jitted at
# module level: every layer of a model calls them with the same shapes and
# static configuration, so the host traces and lowers each once however
# deep the model, and a grad op's forward again is the forward op's
# function (one instruction each after CSE: same operands, same kernel
# payloads).  The router's part is transposed by autodiff inside the
# forward (its vjp's residuals travel with the others); the rest states
# its transpose, since a loop with a traced trip count has none.  What the
# backward can make again over the live chunks alone from what it keeps
# anyway, it does not keep: the gathered rows (from x) and the SwiGLU's
# result (from gu) are N*k-row buffers a layer, and the cells that hold a
# share run within a few hundred MB of the chip's memory.  The barriers tie
# what is made again to the incoming gradient, or the compiler would merge
# it with the forward's and keep that.
_SHARE_STATICS = ("k", "sigmoid", "offset", "norm", "eps", "scaling",
                  "chunk", "kernels", "act")


@functools.partial(jax.jit, static_argnames=_SHARE_STATICS)
def _share_fwd(x2, router_w, bias, w_gu, w_down, *, k, sigmoid, offset, norm,
               eps, scaling, chunk, kernels, act="swiglu"):
    ((top_p, aux), route_vjp, (top_e, counts)) = jax.vjp(
        lambda x2, router_w: _routed(x2, router_w, bias, k, sigmoid, norm,
                                     eps, scaling),
        x2, router_w, has_aux=True)
    with jax.named_scope("dispatch"):
        order, inv, group_sizes = _expert_order(top_e, counts, offset,
                                                w_gu.shape[0])
        n_live = group_sizes.sum()
        x = x2.astype(w_gu.dtype)  # the experts' dtype
        rows = _gather_live(x, order // k, n_live, chunk=chunk)
    with jax.named_scope("experts"):
        gu = _product(kernels[0], rows, w_gu, group_sizes)
        out = _product(kernels[1],
                       _swiglu_live(gu, n_live, chunk=chunk, act=act),
                       w_down, group_sizes)
    with jax.named_scope("combine"):
        y = _weigh_to_tokens(out, top_p, inv, n_live, k=k)
    return (y, counts, aux), (route_vjp, x, w_gu, w_down, top_p, order, inv,
                              group_sizes, n_live, gu, out)


@functools.partial(jax.jit, static_argnames=("k", "chunk", "kernels", "act"))
def _share_bwd(res, g_y, g_aux, *, k, chunk, kernels, act="swiglu"):
    (route_vjp, x, w_gu, w_down, top_p, order, inv, group_sizes, n_live, gu,
     out) = res
    with jax.named_scope("combine"):
        d_out, d_p = _weigh_to_tokens_bwd(out, top_p, order, inv, g_y,
                                          n_live, k=k, chunk=chunk)
    with jax.named_scope("experts"):
        gu, d_out = jax.lax.optimization_barrier((gu, d_out))
        d_act, d_w_down = _product_grads(
            kernels[1], _swiglu_live(gu, n_live, chunk=chunk, act=act),
            w_down, group_sizes, d_out)
        d_gu = _swiglu_live_bwd(gu, d_act, n_live, chunk=chunk, act=act)
    with jax.named_scope("dispatch"):
        x, d_gu = jax.lax.optimization_barrier((x, d_gu))
        rows = _gather_live(x, order // k, n_live, chunk=chunk)
    with jax.named_scope("experts"):
        d_rows, d_w_gu = _product_grads(kernels[0], rows, w_gu, group_sizes,
                                        d_gu)
    with jax.named_scope("dispatch"):
        d_x = _sum_live_slots(d_rows, inv, n_live, k=k)
    d_x2, d_router_w = route_vjp((d_p, g_aux))
    return d_x2 + d_x.astype(d_x2.dtype), d_router_w, d_w_gu, d_w_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _share_layer(x2, router_w, bias, w_gu, w_down, said):
    """(Y [N, d], tokens per expert [E], aux [2]) of x2 [N, d] where the op
    holds a share; `said`: _SHARE_STATICS as a dict's items (hashable)."""
    return _share_fwd(x2, router_w, bias, w_gu, w_down, **dict(said))[0]


def _share_layer_fwd(x2, router_w, bias, w_gu, w_down, said):
    return _share_fwd(x2, router_w, bias, w_gu, w_down, **dict(said))


def _share_layer_bwd(said, res, g):
    said = dict(said)
    d_x2, d_router_w, d_w_gu, d_w_down = _share_bwd(
        res, g[0], g[2], k=said["k"], chunk=said["chunk"],
        kernels=said["kernels"], act=said["act"])
    return d_x2, d_router_w, None, d_w_gu, d_w_down


_share_layer.defvjp(_share_layer_fwd, _share_layer_bwd)


def _router_logits(x2, router_w):
    """Float32 whatever the operands' dtype: a top-k is discontinuous, and
    logits rounded to bf16 change which experts run."""
    return jnp.dot(x2.astype(jnp.float32), router_w.astype(jnp.float32),
                   precision=jax.lax.Precision.HIGHEST)


def _tokens_per_expert(top_e, n_experts):
    # a compare-and-reduce, not a scatter-add of N*k ones
    return (top_e.reshape(-1, 1) == jnp.arange(n_experts)).sum(
        0, dtype=jnp.int32)


def route(x2, router_w, top_k, norm_topk_prob):
    """The softmax router (OLMoE's).  Returns (top-k probabilities [N, k],
    their experts [N, k] int32, tokens per expert [E] int32, aux [2] =
    load-balance and z loss)."""
    n_experts = router_w.shape[-1]
    logits = _router_logits(x2, router_w)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    counts = _tokens_per_expert(top_e, n_experts)
    # lb = E * sum_e F_e * P_e, F_e the routing decisions to e over N (a
    # count: no gradient), P_e the mean router probability; z = mean over
    # tokens of logsumexp(logits)^2
    # (times 1/N as a float: layer_helper.infer_shape evaluates the rule
    # with a batch of a million, and N overflows a weak int32 there)
    frac = jax.lax.stop_gradient(counts.astype(jnp.float32)) * (
        1.0 / x2.shape[0])
    lb = n_experts * jnp.sum(frac * probs.mean(0))
    z = jnp.mean(lse * lse)
    return top_p, top_e, counts, jnp.stack([lb, z])


def route_sigmoid(x2, router_w, bias, top_k, norm_topk_prob, norm_eps=1e-6):
    """The sigmoid router with a selection bias (LFM2's, DeepSeek-V3's
    `noaux_tc` with one group): s = sigmoid(logits) in float32; the
    experts are the top-k of s + bias, their weights the UNBIASED s,
    renormalised over the chosen with the family's `norm_eps` (LFM2
    publishes 1e-6, DeepSeek-V3 1e-20).  The bias is a buffer: no
    gradient.  No auxiliary loss: aux is zeros.  Same returns as
    `route`."""
    n_experts = router_w.shape[-1]
    s = jax.nn.sigmoid(_router_logits(x2, router_w))
    chooser = s if bias is None else s + jax.lax.stop_gradient(
        bias.astype(jnp.float32))
    _, top_e = jax.lax.top_k(chooser, top_k)
    # s at the chosen experts by compare-and-reduce: a take_along_axis
    # would transpose to a scatter-add
    top_p = jnp.where(top_e[..., None] == jnp.arange(n_experts),
                      s[:, None, :], 0.0).sum(-1)
    if norm_topk_prob:
        top_p = top_p / (top_p.sum(-1, keepdims=True) + norm_eps)
    return (top_p, top_e, _tokens_per_expert(top_e, n_experts),
            jnp.zeros((2,), jnp.float32))


@register("moe_ffn", no_grad_inputs=("ExpertBias",),
          stat_outputs=("TokensPerExpert",))
def _moe_ffn(ctx, ins, attrs):
    """Y = sum over a token's top-k experts of p_e * down_e(silu(gate_e x)
    * up_e x).  Inputs: X [..., d], RouterW [d, E], GateUpW [E_held, d, 2f]
    (gate in [..., :f], up in [..., f:]: one grouped matmul reads the
    gathered rows once), DownW [E_held, f, d], optionally ExpertBias [E]
    (sigmoid router: added to the scores for the selection alone).
    Attributes: expert_act "swiglu" (default) or "relu2": the ungated
    expert down_e(relu(up_e x)^2), GateUpW then the up weight alone,
    [E_held, d, f]; top_k, norm_topk_prob, router "softmax" (default) or
    "sigmoid", norm_topk_eps (1e-6: what the sigmoid router adds to the
    chosen scores' sum before it divides), routed_scaling_factor (1: the
    chosen experts' weights are multiplied by it after the
    renormalisation; 1 lowers to no instruction), expert_offset (0): the
    op holds experts [expert_offset, expert_offset + E_held) and leaves
    out what the others would add.  Outputs: Y in the experts' dtype,
    TokensPerExpert [E] int32 (the router's decisions over all E),
    AuxLoss [2] f32 (load-balance, z; zeros for the sigmoid router).  The
    experts compute in GateUpW's dtype (bf16 under AMP) with f32
    accumulation; the router reads X as it is given (f32 under AMP)."""
    x = ins["X"][0]
    router_w = ins["RouterW"][0]
    w_gu, w_down = ins["GateUpW"][0], ins["DownW"][0]
    n_experts, held = router_w.shape[-1], w_gu.shape[0]
    offset = int(attrs.get("expert_offset", 0))
    if offset < 0 or offset + held > n_experts:
        raise ValueError(
            "moe_ffn holds experts [%d, %d) of a router over %d"
            % (offset, offset + held, n_experts))
    sigmoid = attrs.get("router", "softmax") == "sigmoid"
    bias = ins["ExpertBias"][0] if sigmoid and ins.get("ExpertBias") else None
    said = dict(
        k=int(attrs["top_k"]), sigmoid=sigmoid, offset=offset,
        norm=bool(attrs.get("norm_topk_prob", False)),
        eps=float(attrs.get("norm_topk_eps", 1e-6)),
        scaling=float(attrs.get("routed_scaling_factor", 1.0)))
    act = attrs.get("expert_act") or "swiglu"
    if act not in _EXPERT_ACTS:
        raise ValueError("moe_ffn expert_act %r is neither swiglu nor relu2"
                         % (act,))
    x2 = x.reshape(-1, x.shape[-1])
    w_gu, w_down = _kernel_widths(ctx, x2.shape[0] * said["k"], w_gu, w_down,
                                  2 if act == "swiglu" else 1)
    if held == n_experts:
        y, counts, aux = _whole(ctx, x2, router_w, bias, w_gu, w_down,
                                act=act, **said)
    else:
        m, f = x2.shape[0] * said["k"], w_down.shape[1]
        chunk = _chunk_rows(m)
        kernel_tuning.note_live_chunks(m, chunk)
        rows = functools.partial(jax.ShapeDtypeStruct, dtype=w_gu.dtype)
        kernels = (_takes_kernel(ctx, rows((m, x2.shape[1])), w_gu),
                   _takes_kernel(ctx, rows((m, f)), w_down))
        y, counts, aux = _share_layer(
            x2, router_w, bias, w_gu, w_down,
            tuple(dict(said, chunk=chunk, kernels=kernels,
                       act=act).items()))
    return {"Y": [y.reshape(x.shape)], "TokensPerExpert": [counts],
            "AuxLoss": [aux]}


# At this rate an expert chosen twice as often as the mean about halves in
# one step: a chosen count doubles per 0.1 of bias at random weights
# (PERF.md, PR 30).
EXPERT_BIAS_RATE = 0.1


@register("expert_bias_update",
          no_grad_inputs=("ExpertBias", "TokensPerExpert"))
def _expert_bias_update(ctx, ins, attrs):
    """The balancing step of a selection bias (auxiliary-loss-free load
    balancing, Wang et al. 2024, arXiv:2408.15664, in its proportional
    form): after a step, an expert's bias moves against its share of the
    step's routing decisions, b += rate * (1 - c / mean(c)): one chosen
    twice as often as the mean comes down by the rate, one never chosen
    goes up by it.  Attributes: `rate` (EXPERT_BIAS_RATE where the op has
    none) and `max_step` (none: unbounded), which bounds one step to
    [-max_step, +max_step]: over a wide router an expert that took every
    token would else come down by E / k rates at once.  Inputs: ExpertBias
    [E] f32, TokensPerExpert [E] int32 (the step's `moe_ffn` counts over
    all E, whatever share the chip holds).  Output: ExpertBiasOut, the same
    variable."""
    bias = ins["ExpertBias"][0]
    load = ins["TokensPerExpert"][0].astype(jnp.float32)
    step = float(attrs.get("rate", EXPERT_BIAS_RATE)) * (
        1.0 - load / load.mean())
    if attrs.get("max_step") is not None:
        step = jnp.clip(step, -float(attrs["max_step"]),
                        float(attrs["max_step"]))
    return {"ExpertBiasOut": [bias + step.astype(bias.dtype)]}


# ---------------------------------------------------------------------------
# static infer rule (analysis/infer.py)
# ---------------------------------------------------------------------------
from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    register_infer,
    slot_info as _vi,
)


@register_infer("moe_ffn", req_ins=("X", "RouterW", "GateUpW", "DownW"),
                req_outs=("Y", "TokensPerExpert", "AuxLoss"))
def _moe_ffn_infer(op, ins):
    x, wr = _vi(ins, "X"), _vi(ins, "RouterW")
    wgu, wd = _vi(ins, "GateUpW"), _vi(ins, "DownW")
    known = [v is not None and v.shape is not None and min(v.shape) >= 0
             for v in (wr, wgu, wd)]
    n_experts = None
    if all(known):
        n_experts, f = wr.shape[-1], wd.shape[1]
        d, held = wr.shape[0], wd.shape[0]
        relu2 = op.attrs.get("expert_act") == "relu2"
        if (tuple(wgu.shape) != (held, d, f if relu2 else 2 * f)
                or tuple(wd.shape) != (held, f, d)):
            raise InferError(
                "moe_ffn expert weights disagree: RouterW%s GateUpW%s "
                "DownW%s (want [d, E], [E_held, d, %s], [E_held, f, d])"
                % (wr.shape, wgu.shape, wd.shape,
                   "f] under relu2" if relu2 else "2f"))
        offset = int(op.attrs.get("expert_offset", 0))
        if offset < 0 or offset + held > n_experts:
            raise InferError(
                "moe_ffn holds experts [%d, %d) of a router over %d"
                % (offset, offset + held, n_experts))
        if (x is not None and x.shape is not None and x.shape[-1] >= 0
                and x.shape[-1] != d):
            raise InferError("moe_ffn hidden-dim mismatch: X%s vs RouterW%s"
                             % (x.shape, wr.shape))
        if int(op.attrs.get("top_k", 1)) > n_experts:
            raise InferError("moe_ffn top_k %s exceeds its %d experts"
                             % (op.attrs.get("top_k"), n_experts))
        bias = _vi(ins, "ExpertBias")
        if (bias is not None and bias.shape is not None
                and tuple(bias.shape) != (n_experts,)):
            raise InferError("moe_ffn ExpertBias%s is not [%d]"
                             % (bias.shape, n_experts))
    if (op.attrs.get("expert_act") or "swiglu") not in ("swiglu", "relu2"):
        raise InferError("moe_ffn expert_act %r is neither swiglu nor relu2"
                         % (op.attrs.get("expert_act"),))
    if op.attrs.get("router", "softmax") not in ("softmax", "sigmoid"):
        raise InferError("moe_ffn router %r is neither softmax nor sigmoid"
                         % (op.attrs.get("router"),))
    return {
        "Y": [VarInfo(x.shape, wgu.dtype if wgu is not None else None)
              if x is not None else None],
        "TokensPerExpert": [VarInfo((n_experts,), "int32")
                            if n_experts is not None else None],
        "AuxLoss": [VarInfo((2,), "float32")],
    }


@register_infer("expert_bias_update",
                req_ins=("ExpertBias", "TokensPerExpert"),
                req_outs=("ExpertBiasOut",))
def _expert_bias_update_infer(op, ins):
    bias, counts = _vi(ins, "ExpertBias"), _vi(ins, "TokensPerExpert")
    if (bias is not None and counts is not None
            and bias.shape is not None and counts.shape is not None
            and tuple(bias.shape) != tuple(counts.shape)):
        raise InferError("expert_bias_update: ExpertBias%s and "
                         "TokensPerExpert%s differ"
                         % (bias.shape, counts.shape))
    return {"ExpertBiasOut": [bias]}
