"""Routed experts as a Program op: `moe_ffn`.

A token-choice mixture of SwiGLU experts (softmax or sigmoid router,
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
grouped matmuls' work follows the live rows; what those experts would add
is left out, forward and backward.  Nothing stands in for the other chips
or their exchange.

The lowering opens `route`, `dispatch`, `experts` and `combine` under the
op's own `<role>/moe_ffn/<index>` scope, so a device trace splits the op's
time the way `tile_fwd` / `tile_bwd` split the vocabulary head's.
"""

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register


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


def grouped_matmul(ctx, lhs, rhs, group_sizes):
    """[M, K] x [G, K, N] -> [M, N]: rows of `lhs` in G contiguous groups
    of `group_sizes` rows, group g multiplied by rhs[g]; f32 accumulation,
    result in lhs's dtype.  Rows behind the last group belong to none:
    the kernel leaves them unwritten, `ragged_dot` zero, and the caller
    reads neither.  On the chip, megablox's Pallas `gmm` (and `gmm` over
    rhs^T / `tgmm` for the two gradients); elsewhere `jax.lax.ragged_dot`,
    whose transposes jax's autodiff supplies."""
    from .kernel_tuning import note_dense_vjp, note_kernel

    if _megablox_fits(ctx, lhs, rhs):
        note_kernel("grouped_matmul")
        return _megablox_gmm(lhs, rhs, group_sizes)
    note_dense_vjp("grouped_matmul")
    return jax.lax.ragged_dot(lhs, rhs, group_sizes,
                              preferred_element_type=lhs.dtype)


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


@register("moe_ffn", no_grad_inputs=("ExpertBias",))
def _moe_ffn(ctx, ins, attrs):
    """Y = sum over a token's top-k experts of p_e * down_e(silu(gate_e x)
    * up_e x).  Inputs: X [..., d], RouterW [d, E], GateUpW [E_held, d, 2f]
    (gate in [..., :f], up in [..., f:]: one grouped matmul reads the
    gathered rows once), DownW [E_held, f, d], optionally ExpertBias [E]
    (sigmoid router: added to the scores for the selection alone).
    Attributes: top_k, norm_topk_prob, router "softmax" (default) or
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
    k = int(attrs["top_k"])
    d, f = x.shape[-1], w_down.shape[1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    cdt = w_gu.dtype
    n_experts, held = router_w.shape[-1], w_gu.shape[0]
    offset = int(attrs.get("expert_offset", 0))
    if offset < 0 or offset + held > n_experts:
        raise ValueError(
            "moe_ffn holds experts [%d, %d) of a router over %d"
            % (offset, offset + held, n_experts))
    norm = bool(attrs.get("norm_topk_prob", False))

    with jax.named_scope("route"):
        if attrs.get("router", "softmax") == "sigmoid":
            bias = ins["ExpertBias"][0] if ins.get("ExpertBias") else None
            top_p, top_e, counts, aux = route_sigmoid(
                x2, router_w, bias, k, norm,
                float(attrs.get("norm_topk_eps", 1e-6)))
        else:
            top_p, top_e, counts, aux = route(x2, router_w, k, norm)
        scaling = float(attrs.get("routed_scaling_factor", 1.0))
        if scaling != 1.0:
            top_p = top_p * scaling
    with jax.named_scope("dispatch"):
        sort_key, group_sizes, live = top_e.reshape(-1), counts, None
        if held != n_experts:
            # the held experts' rows first, by local expert; assignments
            # to experts held elsewhere behind them, in no group
            local = sort_key - offset
            sort_key = jnp.where((local >= 0) & (local < held), local, held)
            group_sizes = counts[offset:offset + held]
            live = (jnp.arange(n * k) < group_sizes.sum())[:, None]
        # stable sort of the N*k assignments by expert; `inv` undoes it
        order = jnp.argsort(sort_key, stable=True)
        inv = jnp.argsort(order)
        tok = order // k
        rows = _to_expert_order(x2.astype(cdt), tok, inv, k)
        row_p = _to_expert_order(top_p.reshape(n * k, 1), order, inv, 1)
        if live is not None:
            # a dead row's gradient is whatever the kernel left there
            rows = jnp.where(live, rows, 0)
    with jax.named_scope("experts"):
        gu = grouped_matmul(ctx, rows, w_gu, group_sizes)
        act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
               * gu[:, f:].astype(jnp.float32)).astype(cdt)
        out = grouped_matmul(ctx, act, w_down, group_sizes)
    with jax.named_scope("combine"):
        if live is not None:
            out = jnp.where(live, out, 0)  # so is a dead row's result
        out = (out.astype(jnp.float32) * row_p).astype(cdt)
        y = _from_expert_order(out, tok, inv, k)
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
    step's routing decisions, b += EXPERT_BIAS_RATE * (1 - c / mean(c)):
    one chosen twice as often as the mean comes down by the rate, one
    never chosen goes up by it.  Inputs: ExpertBias [E] f32, TokensPerExpert [E] int32 (the
    step's `moe_ffn` counts over all E, whatever share the chip holds).
    Output: ExpertBiasOut, the same variable."""
    bias = ins["ExpertBias"][0]
    load = ins["TokensPerExpert"][0].astype(jnp.float32)
    step = EXPERT_BIAS_RATE * (1.0 - load / load.mean())
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
        if (tuple(wgu.shape) != (held, d, 2 * f)
                or tuple(wd.shape) != (held, f, d)):
            raise InferError(
                "moe_ffn expert weights disagree: RouterW%s GateUpW%s "
                "DownW%s (want [d, E], [E_held, d, 2f], [E_held, f, d])"
                % (wr.shape, wgu.shape, wd.shape))
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
