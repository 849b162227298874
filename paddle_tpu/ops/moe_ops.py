"""Routed experts as a Program op: `moe_ffn`.

A token-choice mixture of SwiGLU experts (softmax router, top-k, no
capacity: dropless under any imbalance) lowered with static shapes: the
N*k (token, expert) assignments are sorted by expert, the tokens gathered
into one [N*k, d] array, and the experts run as two grouped matmuls over
its contiguous groups, whose sizes are data.  An expert that receives no
token is a group of size zero.  Nothing here is a [tokens, experts,
capacity] tensor (`parallel/moe.py`'s dispatch, which no op lowers to).

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


def _gmm_tile(k, n):
    tk = min(k, _GMM_MAX_CONTRACTION)
    return (_GMM_ROWS, tk, min(n, _GMM_WEIGHT_TILE // tk))


def _megablox_fits(lhs, rhs):
    """The Pallas grouped matmul is for the chip (it would be interpreted
    elsewhere), for a single device (XLA cannot partition a Mosaic call
    under a GSPMD mesh), and for rows and widths its tiles divide."""
    from .spmd_epilogue import mesh_ctx

    return (jax.default_backend() == "tpu" and mesh_ctx() is None
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
                 (_GMM_ROWS, min(k, _TGMM_MAX), min(n, _TGMM_MAX)),
                 num_actual_groups=rhs.shape[0])
    return d_lhs, d_rhs, None


_megablox_gmm.defvjp(_mgmm_fwd, _mgmm_bwd)


def grouped_matmul(lhs, rhs, group_sizes):
    """[M, K] x [G, K, N] -> [M, N]: rows of `lhs` in G contiguous groups
    of `group_sizes` rows, group g multiplied by rhs[g]; f32 accumulation,
    result in lhs's dtype.  On the chip, megablox's Pallas `gmm` (and
    `gmm` over rhs^T / `tgmm` for the two gradients); elsewhere
    `jax.lax.ragged_dot`, whose transposes jax's autodiff supplies."""
    from .kernel_tuning import note_dense_vjp, note_kernel

    if _megablox_fits(lhs, rhs):
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


def route(x2, router_w, top_k, norm_topk_prob):
    """Router in float32 whatever the operands' dtype: a top-k is
    discontinuous, and logits rounded to bf16 change which experts run.
    Returns (top-k probabilities [N, k], their experts [N, k] int32,
    tokens per expert [E] int32, aux [2] = load-balance and z loss)."""
    n_experts = router_w.shape[-1]
    logits = jnp.dot(x2.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    probs = jnp.exp(logits - lse[:, None])
    top_p, top_e = jax.lax.top_k(probs, top_k)
    if norm_topk_prob:
        top_p = top_p / top_p.sum(-1, keepdims=True)
    # a compare-and-reduce, not a scatter-add of N*k ones
    counts = (top_e.reshape(-1, 1) == jnp.arange(n_experts)).sum(
        0, dtype=jnp.int32)
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


@register("moe_ffn")
def _moe_ffn(ctx, ins, attrs):
    """Y = sum over a token's top-k experts of p_e * down_e(silu(gate_e x)
    * up_e x).  Inputs: X [..., d], RouterW [d, E], GateUpW [E, d, 2f]
    (gate in [..., :f], up in [..., f:]: one grouped matmul reads the
    gathered rows once), DownW [E, f, d].  Outputs: Y in the experts'
    dtype, TokensPerExpert [E] int32, AuxLoss [2] f32 (load-balance, z).
    The experts compute in GateUpW's dtype (bf16 under AMP) with f32
    accumulation; the router reads X as it is given (f32 under AMP)."""
    x = ins["X"][0]
    router_w = ins["RouterW"][0]
    w_gu, w_down = ins["GateUpW"][0], ins["DownW"][0]
    k = int(attrs["top_k"])
    d, f = x.shape[-1], w_down.shape[1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    cdt = w_gu.dtype

    with jax.named_scope("route"):
        top_p, top_e, counts, aux = route(
            x2, router_w, k, bool(attrs.get("norm_topk_prob", False)))
    with jax.named_scope("dispatch"):
        # stable sort of the N*k assignments by expert; `inv` undoes it
        order = jnp.argsort(top_e.reshape(-1), stable=True)
        inv = jnp.argsort(order)
        tok = order // k
        rows = _to_expert_order(x2.astype(cdt), tok, inv, k)
        row_p = _to_expert_order(top_p.reshape(n * k, 1), order, inv, 1)
    with jax.named_scope("experts"):
        gu = grouped_matmul(rows, w_gu, counts)
        act = (jax.nn.silu(gu[:, :f].astype(jnp.float32))
               * gu[:, f:].astype(jnp.float32)).astype(cdt)
        out = grouped_matmul(act, w_down, counts)
    with jax.named_scope("combine"):
        out = (out.astype(jnp.float32) * row_p).astype(cdt)
        y = _from_expert_order(out, tok, inv, k)
    return {"Y": [y.reshape(x.shape)], "TokensPerExpert": [counts],
            "AuxLoss": [aux]}


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
        d = wr.shape[0]
        if (tuple(wgu.shape) != (n_experts, d, 2 * f)
                or tuple(wd.shape) != (n_experts, f, d)):
            raise InferError(
                "moe_ffn expert weights disagree: RouterW%s GateUpW%s "
                "DownW%s (want [d, E], [E, d, 2f], [E, f, d])"
                % (wr.shape, wgu.shape, wd.shape))
        if (x is not None and x.shape is not None and x.shape[-1] >= 0
                and x.shape[-1] != d):
            raise InferError("moe_ffn hidden-dim mismatch: X%s vs RouterW%s"
                             % (x.shape, wr.shape))
        if int(op.attrs.get("top_k", 1)) > n_experts:
            raise InferError("moe_ffn top_k %s exceeds its %d experts"
                             % (op.attrs.get("top_k"), n_experts))
    return {
        "Y": [VarInfo(x.shape, wgu.dtype if wgu is not None else None)
              if x is not None else None],
        "TokensPerExpert": [VarInfo((n_experts,), "int32")
                            if n_experts is not None else None],
        "AuxLoss": [VarInfo((2,), "float32")],
    }
