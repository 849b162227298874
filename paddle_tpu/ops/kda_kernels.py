"""Pallas TPU kernels for `kda_attention` and `gated_delta_attention`
(ops/kda_ops.py holds the ops and the specification).  The inside of a
chunk: everything a chunk of C = 64 tokens computes without the state it
enters with (`intra`; `gdn_intra` under ONE decay a head), and that
computation transposed (`intra_bwd`, `gdn_intra_bwd`).  The carry, at the
end of the module: what reads the state, with the state held in VMEM across
a head's chunks (`carry`, `carry_bwd`: both members' one).  The inside's
kernels are chunk-parallel: the grid
walks batch, heads and blocks of `block` chunks of one head; q, k, v, g are
read in place from [B, H, T, d] through the BlockSpecs' index maps, and a
grid step holds its chunks in VMEM from the running sum of g to the
results: the elementwise work on [block C, d] rows at once, every product
batched over the block's chunks, so that their dependent chains interleave.

The decayed products sum_c x_t[c] k_i[c] exp(G_t[c] - G_i[c]), i < t, are
made level by level, m = 1, 2, .. 32: at level m the pairs whose positions
first differ in bit m (t in the later, i in the earlier half of a block of
2m tokens) go through the running sum where the later half starts (`ref`,
the last row of the earlier half): (x_t exp(G_t - ref)) . (k_i exp(ref -
G_i)), both factors <= 1 and their product the true value wherever that is
not itself below the smallest float.  A level is ONE product of whole
chunks whose operands are decayed by that level's references, of which the
level's pairs are kept; the references come from G by sublane rolls.  No
exponent is ever positive; t = i needs no decay.

(I + A_kk)^-1 is made from the same levels: blocks of one token are their
own inverse, and two inverted blocks T1, T2 with L below the diagonal
between them make [[T1, 0], [-T2 L T1, T2]]: with `inv` block-diagonal and
L the level's pairs of A_kk that is inv - inv L inv, two products a level
at three bfloat16 passes (`_mm3`: float32 to some 2^-17, XLA's `HIGH`).

The transposed inside makes the inside again, but for the inverse, which
the backward's call of `intra` keeps for it, and transposes it by hand: with M = (I + A_kk)^-1 and R = beta [K exp(G) | V],
dM = [dW | dU0] R^T, dR = M^T [dW | dU0], dA_kk = -M^T dM M^T below the
diagonal; a level's transposed products are products with that level's
decayed operands again; and the decay needs no pass of its own, because G
enters a pair only through exp(G_t - G_i): dG = x (.) dx for the later
operand of a pair and -k (.) dk for the earlier (the references cancel).

Compiled on a TPU, interpreted elsewhere (`pallas_kernels._interpret`).
"""

import functools

import jax
import jax.numpy as jnp

from . import pallas_kernels as _pk
from .pallas_kernels import _mosaic_params, _note, _sds

CHUNK = 64
_F32 = jnp.float32
_BF16 = jnp.bfloat16
_LEVELS = (1, 2, 4, 8, 16, 32)


def _bmm(a, b, dims, dtype):
    """A product batched over the block's chunks, [n, ., .] x [n, ., .]
    contracted over axes `dims` (a's, b's): operands in `dtype`, float32
    accumulation (float32 operands at full precision)."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype),
        (((dims[0],), (dims[1],)), ((0,), (0,))),
        precision=(jax.lax.Precision.HIGHEST if dtype == _F32 else None),
        preferred_element_type=_F32)


def _split(x):
    hi = x.astype(_BF16)
    return hi, x - hi.astype(_F32)


def _mm3(a, b, dims=(2, 1)):
    """float32 a . b at three bfloat16 passes."""
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    return (_bmm(a_hi, b_hi, dims, _BF16)
            + (_bmm(a_hi, b_lo, dims, _BF16) + _bmm(a_lo, b_hi, dims, _BF16)))


def _tri_sum(x, upper):
    """[n, C, d] float32 -> the running sum down a chunk's rows (`upper`:
    from the row to the chunk's end), as products with a triangle of ones
    (exact in bfloat16) of x in three bfloat16 parts: float32's sum."""
    n, c, _ = x.shape
    r = jax.lax.broadcasted_iota(jnp.int32, (n, c, c), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (n, c, c), 2)
    ones = jnp.where((s >= r) if upper else (s <= r), 1.0, 0.0).astype(_BF16)
    hi, rest = _split(x)
    mid, low = _split(rest)
    return (_bmm(ones, hi, (2, 1), _BF16)
            + (_bmm(ones, mid, (2, 1), _BF16) + _bmm(ones, low, (2, 1), _BF16)))


def _roll(x, shift):
    """jnp.roll down the rows: out[r] = x[r - shift]."""
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.roll(x, shift % x.shape[0], 0)


def _pairs(n):
    """The chunk's pairs (t, i) as [n, C, C] masks: t == i, i < t, and for
    every level m those with i < t whose positions first differ in bit
    m."""
    t = jax.lax.broadcasted_iota(jnp.int32, (n, CHUNK, CHUNK), 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (n, CHUNK, CHUNK), 2)
    differ = t ^ i
    return t == i, t > i, [(t > i) & (differ >= m) & (differ < 2 * m)
                           for m in _LEVELS]


def _decays(gsum):
    """gsum [rows, dk], the running sums of the block's chunks stacked:
    -> (for every level (what its later rows are decayed by, exp(G_t -
    ref); what its earlier rows are, exp(ref - G_i)), both [rows, dk] and
    <= 1 on every row (rows that are not the level's are not used); G_C,
    the chunk's last row, on every row of the chunk)."""
    rows = gsum.shape[0]
    pos = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    out, last = [], gsum  # last[r] = G at the last row of r's block of m
    for m in _LEVELS + (CHUNK,):
        if m > 1:
            last = jnp.where((pos & (m // 2)) == 0, _roll(last, -(m // 2)),
                             last)
        if m < CHUNK:
            out.append((jnp.exp(jnp.minimum(gsum - _roll(last, m), 0.0)),
                        jnp.exp(jnp.minimum(last - gsum, 0.0))))
    return out, last


def _column(row, eye):
    """[n, 1, C] -> [n, C, 1] (and, with axes swapped, back) through the
    diagonal: no transpose of a narrow array."""
    return jnp.sum(jnp.where(eye, row, 0.0), axis=2, keepdims=True)


def _row(column, eye):
    return jnp.sum(jnp.where(eye, column, 0.0), axis=1, keepdims=True)


def _inverse(a_kk, eye, levels):
    """(I + A_kk)^-1 [n, C, C] for A_kk strictly lower triangular."""
    inv = jnp.where(eye, 1.0, 0.0) - jnp.where(levels[0], a_kk, 0.0)
    for pairs in levels[1:]:
        inv = inv - _mm3(_mm3(inv, jnp.where(pairs, a_kk, 0.0)), inv)
    return inv


def _chunk_row(x):
    """[n, C, d] whose rows are alike within a chunk -> [n, 1, d]."""
    return jnp.max(x, axis=1, keepdims=True)


def _flat(x):
    return x.reshape(x.shape[0] * x.shape[1], x.shape[2])


def _intra_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                  w_ref, u0_ref, a_ref, qg_ref, kd_ref, gamma_ref,
                  *solve_ref, scale):
    dtype = q_ref.dtype
    n, c = w_ref.shape[0], CHUNK
    shape = (n, c, q_ref.shape[-1])
    gsum = _flat(_tri_sum(g_ref[...].astype(_F32).reshape(shape), False))
    qf = q_ref[...].astype(_F32) * scale
    kf = k_ref[...].astype(_F32)
    eye, _, levels = _pairs(n)
    decays, last = _decays(gsum)
    a_qk = jnp.where(eye, jnp.sum(qf * kf, -1, keepdims=True).reshape(
        n, c, 1), 0.0)
    l_kk = jnp.zeros((n, c, c), _F32)
    for pairs, (later, earlier) in zip(levels, decays):
        here = jnp.concatenate([(qf * later).reshape(shape),
                                (kf * later).reshape(shape)], 1)
        below = _bmm(here, (kf * earlier).reshape(shape), (2, 2), dtype)
        a_qk = jnp.where(pairs, below[:, :c], a_qk)
        l_kk = jnp.where(pairs, below[:, c:], l_kk)
    beta = _column(beta_ref[...], eye)
    solve = _inverse(beta * l_kk, eye, levels)
    into = jnp.exp(gsum)
    w_ref[...] = _bmm(solve, beta * (kf * into).reshape(shape), (2, 1),
                      dtype).astype(dtype)
    u0_ref[...] = _bmm(
        solve, beta * v_ref[...].astype(_F32).reshape(n, c, v_ref.shape[-1]),
        (2, 1), dtype)
    a_ref[...] = a_qk.astype(dtype)
    qg_ref[...] = (qf * into).reshape(shape).astype(dtype)
    kd_ref[...] = (kf * jnp.exp(last - gsum)).reshape(shape).astype(dtype)
    gamma_ref[...] = _chunk_row(jnp.exp(last).reshape(shape))
    for ref in solve_ref:  # the backward's call: kept for `intra_bwd`
        ref[...] = solve


def _intra_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref,
                      dw_ref, du0_ref, da_ref, dqg_ref, dkd_ref, dgamma_ref,
                      dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, *, scale):
    dtype = q_ref.dtype
    n, c = dw_ref.shape[0], CHUNK
    shape = (n, c, q_ref.shape[-1])
    gsum = _flat(_tri_sum(g_ref[...].astype(_F32).reshape(shape), False))
    qf = q_ref[...].astype(_F32) * scale
    kf = k_ref[...].astype(_F32)
    vf = v_ref[...].astype(_F32)
    eye, lower, levels = _pairs(n)
    decays, last = _decays(gsum)
    # the inside again, as far as the transposition reads it (the inverse
    # is kernel 1's: ten dependent three-pass products, a third of this
    # kernel's time when it made them again)
    l_kk = jnp.zeros((n, c, c), _F32)
    for pairs, (later, earlier) in zip(levels, decays):
        l_kk = jnp.where(pairs, _bmm(
            (kf * later).reshape(shape), (kf * earlier).reshape(shape),
            (2, 2), dtype), l_kk)
    beta3 = _column(beta_ref[...], eye)
    beta = _flat(beta3)
    solve = solve_ref[...]
    into = jnp.exp(gsum)
    out = jnp.exp(last - gsum)
    k_into = kf * into
    # [W | U0] = solve . beta [K exp(G) | V]
    dw, du0 = dw_ref[...], du0_ref[...]
    d_solve = (_bmm(dw, (beta * k_into).reshape(shape), (2, 2), dtype)
               + _bmm(du0, (beta * vf).reshape(du0.shape), (2, 2), dtype))
    d_rk = _flat(_bmm(solve, dw, (1, 1), dtype))
    d_rv = _flat(_bmm(solve, du0, (1, 1), dtype))
    d_akk = jnp.where(
        lower, -_mm3(_mm3(solve, d_solve, (1, 1)), solve, (2, 2)), 0.0)
    d_lkk = beta3 * d_akk
    d_aqk = da_ref[...].astype(_F32)
    dbeta = (jnp.sum(d_akk * l_kk, 2, keepdims=True)
             + jnp.sum(d_rk * k_into, -1, keepdims=True).reshape(n, c, 1)
             + jnp.sum(d_rv * vf, -1, keepdims=True).reshape(n, c, 1))
    # the decayed products, level by level: as the later operand of a
    # pair (dq_l, dk_l) and as the earlier one (dk_e)
    dq_l = jnp.zeros_like(qf)
    dk_l = jnp.zeros_like(kf)
    dk_e = jnp.zeros_like(kf)
    for pairs, (later, earlier) in zip(levels, decays):
        d_below = jnp.concatenate([jnp.where(pairs, d_aqk, 0.0),
                                   jnp.where(pairs, d_lkk, 0.0)], 1)
        here = jnp.concatenate([(qf * later).reshape(shape),
                                (kf * later).reshape(shape)], 1)
        d_here = _bmm(d_below, (kf * earlier).reshape(shape), (2, 1), dtype)
        dq_l = dq_l + _flat(d_here[:, :c]) * later
        dk_l = dk_l + _flat(d_here[:, c:]) * later
        dk_e = dk_e + _flat(_bmm(d_below, here, (1, 1), dtype)) * earlier
    on_diagonal = _flat(jnp.sum(jnp.where(eye, d_aqk, 0.0), 2,
                                keepdims=True))
    d_qg = dqg_ref[...].astype(_F32).reshape(qf.shape)
    d_kd = dkd_ref[...].astype(_F32).reshape(kf.shape)
    dq_ref[...] = (scale * (dq_l + on_diagonal * kf + d_qg * into)).astype(
        dq_ref.dtype)
    dk_ref[...] = (dk_l + dk_e + on_diagonal * qf + beta * into * d_rk
                   + d_kd * out).astype(dk_ref.dtype)
    dv_ref[...] = (beta * d_rv).astype(dv_ref.dtype)
    leaving = d_kd * kf * out  # through exp(G_C - G)
    d_gsum = (qf * dq_l + kf * (dk_l - dk_e) + (d_qg * qf + beta * kf * d_rk)
              * into - leaving)
    d_last = (jnp.sum(leaving.reshape(shape), 1, keepdims=True)
              + dgamma_ref[...] * _chunk_row(jnp.exp(last).reshape(shape)))
    dg_ref[...] = _flat(_tri_sum(d_gsum.reshape(shape), True) + d_last)
    dbeta_ref[...] = _row(dbeta, eye)


def _specs(b, h, t, block):
    """(grid, the BlockSpec of a [B, H, T, d] array's block of chunks, of a
    [N, B, H, ., d] array's, of beta's [B, H, N, 1, C])."""
    from jax.experimental import pallas as pl

    def tokens(d):
        return pl.BlockSpec((None, None, block * CHUNK, d),
                            lambda i, j, l: (i, j, l, 0))

    def parts(rows, d):
        return pl.BlockSpec((block, None, None, rows, d),
                            lambda i, j, l: (l, i, j, 0, 0))

    return ((b, h, t // (block * CHUNK)), tokens, parts,
            pl.BlockSpec((None, None, block, 1, CHUNK),
                         lambda i, j, l: (i, j, l, 0, 0)))


def intra(q, k, v, g, beta, scale, block, keep_solve=False):
    """q, k, g [B, H, T, dk], v [B, H, T, dv], beta [B, H, T], T a multiple
    of `block` chunks -> (W, U0, A_qk, Q exp(G), K exp(G_C - G), exp(G_C)),
    chunks leading ([N, B, H, C, .]; exp(G_C) [N, B, H, dk]): the operands
    of the carry's products in q's dtype, U0 and the chunk's whole decay
    float32; with `keep_solve` (I + A_kk)^-1 [N, B, H, C, C] float32 after
    them, for `intra_bwd`."""
    from jax.experimental import pallas as pl

    b, h, t, dk = q.shape
    dv, c, chunks = v.shape[-1], CHUNK, t // CHUNK
    grid, tokens, parts, beta_spec = _specs(b, h, t, block)
    _note("kda_intra")
    out = pl.pallas_call(
        functools.partial(_intra_kernel, scale=scale),
        grid=grid,
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), beta_spec],
        out_specs=[parts(c, dk), parts(c, dv), parts(c, c), parts(c, dk),
                   parts(c, dk), parts(1, dk)] + [parts(c, c)] * keep_solve,
        out_shape=[_sds((chunks, b, h, c, dk), q.dtype, q),
                   _sds((chunks, b, h, c, dv), _F32, q),
                   _sds((chunks, b, h, c, c), q.dtype, q),
                   _sds((chunks, b, h, c, dk), q.dtype, q),
                   _sds((chunks, b, h, c, dk), q.dtype, q),
                   _sds((chunks, b, h, 1, dk), _F32, q)]
        + [_sds((chunks, b, h, c, c), _F32, q)] * keep_solve,
        interpret=_pk._interpret(),
        compiler_params=_mosaic_params(),
    )(q, k, v, g, beta.astype(_F32).reshape(b, h, chunks, 1, c))
    return (tuple(out[:5]) + (out[5].reshape(chunks, b, h, dk),)
            + tuple(out[6:]))


def intra_bwd(q, k, v, g, beta, solve, d_parts, scale, block):
    """`intra` transposed: its inputs, the inverse it kept and the six
    results' gradients (as `intra` lays them out) -> the gradients of q,
    k, v (in their dtypes), g and beta (float32)."""
    from jax.experimental import pallas as pl

    b, h, t, dk = q.shape
    dv, c, chunks = v.shape[-1], CHUNK, t // CHUNK
    dw, du0, da, dqg, dkd, dgamma = d_parts
    grid, tokens, parts, beta_spec = _specs(b, h, t, block)
    _note("kda_intra_bwd")
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_intra_bwd_kernel, scale=scale),
        grid=grid,
        in_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), beta_spec,
                  parts(c, c), parts(c, dk), parts(c, dv), parts(c, c),
                  parts(c, dk), parts(c, dk), parts(1, dk)],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), tokens(dk), beta_spec],
        out_shape=[_sds(q.shape, q.dtype, q), _sds(k.shape, k.dtype, q),
                   _sds(v.shape, v.dtype, q), _sds(g.shape, _F32, q),
                   _sds((b, h, chunks, 1, c), _F32, q)],
        interpret=_pk._interpret(),
        compiler_params=_mosaic_params(),
    )(q, k, v, g, beta.astype(_F32).reshape(b, h, chunks, 1, c), solve,
      dw, du0, da, dqg, dkd, dgamma.astype(_F32).reshape(chunks, b, h, 1, dk))
    return dq, dk_, dv_, dg, dbeta.reshape(b, h, t)


# ---------------------------------------------------------------------------
# the family's second member: ONE decay a head (`gated_delta_attention`)
# ---------------------------------------------------------------------------
# With a scalar log-decay the chunk's D(t, i) = exp(G_t - G_i) is a [C, C]
# matrix OUTSIDE the contraction: Q K^T and K K^T are one product each
# (here one product of [Q; K] against K), multiplied by D afterwards; the
# exponent is the difference itself, <= 0 wherever the pair is kept, so no
# level and no reference is needed for the decay.  The inverse is the
# per-channel kernel's (`_inverse`, by the same levels).  g and beta come
# in as rows [.., 1, C] (their chunk on the lanes), and a row turns into a
# column through the diagonal (`_column` / `_row`).  Key heads are shared:
# value head j reads q and k of head j // (Hv / Hk) through the BlockSpecs'
# index maps, nothing is repeated in HBM.


def _lanes(x):
    """[B, H, T] -> [B, H, N, 1, C] float32: a chunk's numbers on the
    lanes."""
    return x.astype(_F32).reshape(x.shape[:2] + (-1, 1, CHUNK))


def _gdn_decay(g_row, eye, lower):
    """g_row [n, 1, C] -> (G as a column [n, C, 1], D [n, C, C] = exp(G_t -
    G_i) for i <= t (1 on the diagonal, <= 1 below; above it the clamped
    exponent's 1, never kept), G_C [n, 1, 1])."""
    upto = jnp.sum(jnp.where(lower | eye, g_row, 0.0), axis=2, keepdims=True)
    diff = upto - _row(upto, eye)
    # the chunk's last row by a masked sum: Mosaic refuses a one-row
    # sublane slice
    last = jnp.sum(jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, upto.shape, 1) == CHUNK - 1,
        upto, 0.0), axis=1, keepdims=True)
    return upto, jnp.exp(jnp.minimum(diff, 0.0)), last


def _gdn_inside(q_ref, k_ref, g_ref, scale, n):
    """What both kernels make first: masks, q (scaled) and k [n, C, dk]
    float32, the decay's pieces, and the two decayed products."""
    dtype = q_ref.dtype
    shape = (n, CHUNK, q_ref.shape[-1])
    eye, lower, levels = _pairs(n)
    qf = q_ref[...].astype(_F32).reshape(shape) * scale
    kf = k_ref[...].astype(_F32).reshape(shape)
    gsum, decay, last = _gdn_decay(g_ref[...], eye, lower)
    both = _bmm(jnp.concatenate([qf, kf], 1), kf, (2, 2), dtype)
    a_qk = jnp.where(lower | eye, both[:, :CHUNK] * decay, 0.0)
    l_kk = jnp.where(lower, both[:, CHUNK:] * decay, 0.0)
    return (eye, lower, levels), qf, kf, (gsum, decay, last), a_qk, l_kk


def _gdn_intra_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref,
                      w_ref, u0_ref, a_ref, qg_ref, kd_ref, gamma_ref,
                      *solve_ref, scale):
    dtype = q_ref.dtype
    n, c = w_ref.shape[0], CHUNK
    (eye, _, levels), qf, kf, (gsum, _, last), a_qk, l_kk = _gdn_inside(
        q_ref, k_ref, g_ref, scale, n)
    beta = _column(beta_ref[...], eye)
    solve = _inverse(beta * l_kk, eye, levels)
    into = jnp.exp(gsum)
    w_ref[...] = _bmm(solve, beta * into * kf, (2, 1), dtype).astype(dtype)
    u0_ref[...] = _bmm(
        solve, beta * v_ref[...].astype(_F32).reshape(n, c, v_ref.shape[-1]),
        (2, 1), dtype)
    a_ref[...] = a_qk.astype(dtype)
    qg_ref[...] = (qf * into).astype(dtype)
    kd_ref[...] = (kf * jnp.exp(last - gsum)).astype(dtype)
    gamma_ref[...] = jnp.broadcast_to(jnp.exp(last), gamma_ref.shape)
    for ref in solve_ref:  # the backward's call: kept for the transpose
        ref[...] = solve


def _gdn_intra_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, solve_ref,
                          dw_ref, du0_ref, da_ref, dqg_ref, dkd_ref,
                          dgamma_ref, dq_ref, dk_ref, dv_ref, dg_ref,
                          dbeta_ref, *, scale):
    dtype = q_ref.dtype
    n, c = dw_ref.shape[0], CHUNK
    ((eye, lower, _), qf, kf, (gsum, decay, last), a_qk,
     l_kk) = _gdn_inside(q_ref, k_ref, g_ref, scale, n)
    vf = v_ref[...].astype(_F32).reshape(n, c, v_ref.shape[-1])
    beta = _column(beta_ref[...], eye)
    solve = solve_ref[...]
    into = jnp.exp(gsum)
    out = jnp.exp(last - gsum)
    k_into = kf * into
    # [W | U0] = solve . beta [K exp(G) | V]
    dw, du0 = dw_ref[...], du0_ref[...]
    d_solve = (_bmm(dw, beta * k_into, (2, 2), dtype)
               + _bmm(du0, beta * vf, (2, 2), dtype))
    d_rk = _bmm(solve, dw, (1, 1), dtype)
    d_rv = _bmm(solve, du0, (1, 1), dtype)
    d_akk = jnp.where(
        lower, -_mm3(_mm3(solve, d_solve, (1, 1)), solve, (2, 2)), 0.0)
    d_lkk = beta * d_akk
    d_aqk = jnp.where(lower | eye, da_ref[...].astype(_F32), 0.0)
    dbeta = (jnp.sum(d_akk * l_kk, 2, keepdims=True)
             + jnp.sum(d_rk * k_into, -1, keepdims=True)
             + jnp.sum(d_rv * vf, -1, keepdims=True))
    # the two decayed products: [dQK; dKK] against k for the later operand
    # of a pair, transposed against [q; k] for the earlier one
    d_both = jnp.concatenate([d_aqk * decay, d_lkk * decay], 1)
    d_later = _bmm(d_both, kf, (2, 1), dtype)
    d_earlier = _bmm(d_both, jnp.concatenate([qf, kf], 1), (1, 1), dtype)
    d_qg = dqg_ref[...].astype(_F32)
    d_kd = dkd_ref[...].astype(_F32)
    dq_ref[...] = (scale * (d_later[:, :c] + d_qg * into)).reshape(
        dq_ref.shape).astype(dq_ref.dtype)
    dk_ref[...] = (d_later[:, c:] + d_earlier + beta * into * d_rk
                   + d_kd * out).reshape(dk_ref.shape).astype(dk_ref.dtype)
    dv_ref[...] = (beta * d_rv).reshape(dv_ref.shape).astype(dv_ref.dtype)
    # G enters a pair only through exp(G_t - G_i): + for the later token,
    # - for the earlier, of the pair's gradient times its decayed value
    pairs = d_lkk * l_kk + d_aqk * a_qk
    leaving = jnp.sum(d_kd * kf * out, -1, keepdims=True)  # exp(G_C - G)
    d_gsum = (jnp.sum((d_qg * qf + beta * kf * d_rk) * into, -1,
                      keepdims=True) - leaving
              + jnp.sum(pairs, 2, keepdims=True)
              - _column(jnp.sum(pairs, 1, keepdims=True), eye))
    d_last = (jnp.sum(leaving, 1, keepdims=True)
              + jnp.max(dgamma_ref[...], 2, keepdims=True) * jnp.exp(last))
    # the running sum transposed: from the row to the chunk's end
    dg_ref[...] = jnp.sum(jnp.where(lower | eye, d_gsum, 0.0), axis=1,
                          keepdims=True) + d_last
    dbeta_ref[...] = _row(dbeta, eye)


def _gdn_specs(b, h, t, block, rep):
    """As `_specs`, with q and k read from key head j // rep and a row
    [B, H, N, 1, C] spec for g as for beta."""
    from jax.experimental import pallas as pl

    grid, tokens, parts, rows = _specs(b, h, t, block)

    def keys(d):
        return pl.BlockSpec((None, None, block * CHUNK, d),
                            lambda i, j, l: (i, j // rep, l, 0))

    return grid, keys, tokens, parts, rows


def gdn_intra(q, k, v, g, beta, scale, block, keep_solve=False):
    """q, k [B, Hk, T, dk], v [B, Hv, T, dv], g, beta [B, Hv, T], T a
    multiple of `block` chunks, Hk dividing Hv -> `intra`'s six results for
    the Hv value heads, the chunk's whole decay [N, B, Hv, 1]."""
    from jax.experimental import pallas as pl

    b, hv, t, dv = v.shape
    dk, c, chunks = q.shape[-1], CHUNK, t // CHUNK
    grid, keys, tokens, parts, rows = _gdn_specs(b, hv, t, block,
                                                 hv // q.shape[1])
    _note("gdn_intra")
    out = pl.pallas_call(
        functools.partial(_gdn_intra_kernel, scale=scale),
        grid=grid,
        in_specs=[keys(dk), keys(dk), tokens(dv), rows, rows],
        out_specs=[parts(c, dk), parts(c, dv), parts(c, c), parts(c, dk),
                   parts(c, dk), parts(1, c)] + [parts(c, c)] * keep_solve,
        out_shape=[_sds((chunks, b, hv, c, dk), q.dtype, q),
                   _sds((chunks, b, hv, c, dv), _F32, q),
                   _sds((chunks, b, hv, c, c), q.dtype, q),
                   _sds((chunks, b, hv, c, dk), q.dtype, q),
                   _sds((chunks, b, hv, c, dk), q.dtype, q),
                   _sds((chunks, b, hv, 1, c), _F32, q)]
        + [_sds((chunks, b, hv, c, c), _F32, q)] * keep_solve,
        interpret=_pk._interpret(),
        compiler_params=_mosaic_params(),
    )(q, k, v, _lanes(g), _lanes(beta))
    return tuple(out[:5]) + (out[5][..., 0, :1],) + tuple(out[6:])


def gdn_intra_bwd(q, k, v, g, beta, solve, d_parts, scale, block):
    """`gdn_intra` transposed -> the gradients of q and k PER VALUE HEAD
    ([B, Hv, T, dk]: the caller sums a key head's readers), of v, and of g
    and beta (float32, [B, Hv, T])."""
    from jax.experimental import pallas as pl

    b, hv, t, dv = v.shape
    dk, c, chunks = q.shape[-1], CHUNK, t // CHUNK
    dw, du0, da, dqg, dkd, dgamma = d_parts
    grid, keys, tokens, parts, rows = _gdn_specs(b, hv, t, block,
                                                 hv // q.shape[1])
    _note("gdn_intra_bwd")
    dq, dk_, dv_, dg, dbeta = pl.pallas_call(
        functools.partial(_gdn_intra_bwd_kernel, scale=scale),
        grid=grid,
        in_specs=[keys(dk), keys(dk), tokens(dv), rows, rows,
                  parts(c, c), parts(c, dk), parts(c, dv), parts(c, c),
                  parts(c, dk), parts(c, dk), parts(1, c)],
        out_specs=[tokens(dk), tokens(dk), tokens(dv), rows, rows],
        out_shape=[_sds((b, hv, t, dk), q.dtype, q),
                   _sds((b, hv, t, dk), k.dtype, q),
                   _sds(v.shape, v.dtype, q),
                   _sds((b, hv, chunks, 1, c), _F32, q),
                   _sds((b, hv, chunks, 1, c), _F32, q)],
        interpret=_pk._interpret(),
        compiler_params=_mosaic_params(),
    )(q, k, v, _lanes(g), _lanes(beta), solve, dw, du0, da, dqg, dkd,
      jnp.broadcast_to(dgamma.astype(_F32)[..., None], (chunks, b, hv, 1, c)))
    return dq, dk_, dv_, dg.reshape(b, hv, t), dbeta.reshape(b, hv, t)


# ---------------------------------------------------------------------------
# the carry: the state across a head's chunks, both members
# ---------------------------------------------------------------------------
# What is left of a chunk once `intra` / `gdn_intra` has run reads the
# state S the chunk enters with: U = U0 - W S, O = (Q exp(G)) S + A_qk U,
# S' = gamma S + (K exp(G_C - G))^T U.  The grid is (groups of `heads`
# heads, chunks), the chunk axis last and sequential (`_carry_params`
# says so to the compiler), and S lives in a VMEM scratch from a head's
# first chunk to its last: float32, and TRANSPOSED, [dv, dk], so that the
# chunk's whole decay (a row [1, dk] under a per-channel decay, [1, 1]
# under one a head: the kernels broadcast what they are given) scales its
# lanes and the decay's gradient, sum(S . dS) over dv, is a sum down the
# sublanes: no narrow array is ever turned.  A grid step holds `heads`
# heads: their chains of dependent 64-row products interleave, as the
# chunks of a block do in `intra`.  The pipeline fetches the next chunk's
# operands under this chunk's products.  Leading axes are merged
# ([N, B, H, ..] -> [N, B H, ..]: no copy), the parts are read where
# `intra` wrote them and O is written in place in [B H, T, dv].
#
# The backward walks twice: `carry(keep_states=True)` forward, writing the
# state every chunk ENTERED with ([N, B H, dv, dk] float32: its gradient
# through the chunk's whole decay sums s . ds elementwise) and U (a
# product's operand, in the operands' dtype), then `carry_bwd` from the
# last chunk to the first (the index maps read chunk N - 1 - n) with dS in
# the scratch.  A visit makes everything the chunk owes, the three products
# that do not wait for dS among them (dW, dA_qk, d(Q exp(G)): S and U are
# in VMEM already, so a state is read once).


def _carry_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=_pk._VMEM_LIMIT_BYTES)


def _carry_kernel(*refs, keep_states):
    from jax.experimental import pallas as pl

    state = refs[-1]  # S^T [heads, dv, dk] float32
    if keep_states:  # the backward's first walk reads what U is made of
        w_ref, u0_ref, kd_ref, gamma_ref, s_ref, u_ref = refs[:-1]
    else:
        w_ref, u0_ref, a_ref, qg_ref, kd_ref, gamma_ref, o_ref = refs[:-1]
    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    s_op = s.astype(dtype)  # as a product's operand
    if keep_states:
        s_ref[...] = s
        u = (u0_ref[...] - _bmm(w_ref[...], s_op, (2, 2), dtype)).astype(
            dtype)
        u_ref[...] = u
    else:
        # [W; Q exp(G)] S: one product of 128 rows
        reads = _bmm(jnp.concatenate([w_ref[...], qg_ref[...]], 1), s_op,
                     (2, 2), dtype)
        u = (u0_ref[...] - reads[:, :CHUNK]).astype(dtype)
        o_ref[...] = (reads[:, CHUNK:] + _bmm(a_ref[...], u, (2, 1), dtype)
                      ).astype(o_ref.dtype)
    state[...] = gamma_ref[...] * s + _bmm(u, kd_ref[...], (1, 1), dtype)


def _carry_bwd_kernel(w_ref, a_ref, qg_ref, kd_ref, gamma_ref, s_ref, u_ref,
                      do_ref, dw_ref, du_ref, da_ref, dqg_ref, dkd_ref,
                      dgamma_ref, d_state):
    from jax.experimental import pallas as pl

    dtype = w_ref.dtype

    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        d_state[...] = jnp.zeros_like(d_state)

    ds = d_state[...]  # dS^T [heads, dv, dk] float32
    ds_op = ds.astype(dtype)  # as a product's operand
    s, u, d_o = s_ref[...], u_ref[...], do_ref[...]
    du = (_bmm(a_ref[...], d_o, (1, 1), dtype)
          + _bmm(kd_ref[...], ds_op, (2, 2), dtype)).astype(dtype)
    du_ref[...] = du
    dkd_ref[...] = _bmm(u, ds_op, (2, 1), dtype).astype(dtype)
    dgamma_ref[...] = jnp.sum(s * ds, axis=1, keepdims=True)
    # [-dU; dO] S^T -> [dW; d(Q exp(G))]: one product of 128 rows
    reads = _bmm(jnp.concatenate([-du, d_o], 1), s, (2, 1), dtype)
    dw_ref[...] = reads[:, :CHUNK].astype(dtype)
    dqg_ref[...] = reads[:, CHUNK:].astype(dtype)
    da_ref[...] = _bmm(d_o, u, (2, 2), dtype).astype(dtype)
    d_state[...] = (_bmm(d_o, qg_ref[...], (1, 1), dtype)
                    + gamma_ref[...] * ds
                    - _bmm(du, w_ref[...], (1, 1), dtype))


def _carry_specs(n, bh, heads, reverse=False):
    """(grid, the BlockSpec of a [N, B H, rows, d] array's chunk of `heads`
    heads, of a [B H, T, d] array's); `reverse`: from the last chunk to the
    first."""
    from jax.experimental import pallas as pl

    def at(l):
        return n - 1 - l if reverse else l

    def parts(rows, d):
        return pl.BlockSpec((None, heads, rows, d),
                            lambda i, l: (at(l), i, 0, 0))

    def tokens(d):
        return pl.BlockSpec((heads, CHUNK, d), lambda i, l: (i, at(l), 0))

    return (bh // heads, n), parts, tokens


def _heads_merged(x):
    """[N, B, H, rows, d] -> [N, B H, rows, d]."""
    return x.reshape(x.shape[:1] + (-1,) + x.shape[3:])


def _carry_operands(parts):
    """The six parts with their heads merged; the chunk's whole decay
    [N, B, H, dk or 1] as rows [N, B H, 1, dk or 1]."""
    return ([_heads_merged(x) for x in parts[:5]]
            + [_heads_merged(parts[5][:, :, :, None])])


def carry(parts, out_dtype, heads, keep_states=False):
    """An inside's six results, chunks leading ([N, B, H, C, .]; the chunk's
    whole decay [N, B, H, dk] under a per-channel decay, [N, B, H, 1] under
    one a head), `heads` dividing B H -> O [B, H, N C, dv] in `out_dtype`;
    with `keep_states`, for `carry_bwd`: (the state every chunk entered
    with, transposed, [N, B H, dv, dk] float32; U [N, B H, C, dv] in the
    operands' dtype)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = parts[0]
    n, b, h, c, dk = w.shape
    dv, bh = parts[1].shape[-1], b * h
    ins = _carry_operands(parts)
    grid, chunk, tokens = _carry_specs(n, bh, heads)
    _note("kda_carry")
    in_specs = [chunk(c, dk), chunk(c, dv), chunk(c, c), chunk(c, dk),
                chunk(c, dk), chunk(1, ins[5].shape[-1])]
    if keep_states:
        ins, in_specs = (x[:2] + x[4:] for x in (ins, in_specs))
        out_specs = [chunk(dv, dk), chunk(c, dv)]
        out_shape = [_sds((n, bh, dv, dk), _F32, w),
                     _sds((n, bh, c, dv), w.dtype, w)]
    else:
        out_specs = tokens(dv)
        out_shape = _sds((bh, n * c, dv), out_dtype, w)
    out = pl.pallas_call(
        functools.partial(_carry_kernel, keep_states=keep_states),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        interpret=_pk._interpret(),
        compiler_params=_carry_params(),
    )(*ins)
    return out if keep_states else out.reshape(b, h, n * c, dv)


def carry_bwd(parts, states, u, do, heads):
    """`carry` transposed: the parts, what `carry(keep_states=True)` kept
    and the result's gradient [B, H, N C, dv] -> the six parts' gradients,
    chunks leading as `intra_bwd` / `gdn_intra_bwd` take them (the last,
    the chunk's whole decay's, [N, B, H, dk]: a decay of one number a head
    sums it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    w = parts[0]
    n, b, h, c, dk = w.shape
    dv, bh, dtype = parts[1].shape[-1], b * h, w.dtype
    ins = _carry_operands(parts)
    grid, chunk, tokens = _carry_specs(n, bh, heads, reverse=True)
    _note("kda_carry_bwd")
    out = pl.pallas_call(
        _carry_bwd_kernel,
        grid=grid,
        in_specs=[chunk(c, dk), chunk(c, c), chunk(c, dk), chunk(c, dk),
                  chunk(1, ins[5].shape[-1]), chunk(dv, dk), chunk(c, dv),
                  tokens(dv)],
        out_specs=[chunk(c, dk), chunk(c, dv), chunk(c, c), chunk(c, dk),
                   chunk(c, dk), chunk(1, dk)],
        out_shape=[_sds((n, bh, c, dk), dtype, w),
                   _sds((n, bh, c, dv), dtype, w),
                   _sds((n, bh, c, c), dtype, w),
                   _sds((n, bh, c, dk), dtype, w),
                   _sds((n, bh, c, dk), dtype, w),
                   _sds((n, bh, 1, dk), _F32, w)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), _F32)],
        # a visit reads and writes the same chunk: W, A_qk, Q exp(G),
        # K exp(G_C - G) and U give their memory to their gradients
        input_output_aliases={0: 0, 1: 2, 2: 3, 3: 4, 6: 1},
        interpret=_pk._interpret(),
        compiler_params=_carry_params(),
    )(ins[0], *ins[2:], states, u, do.astype(dtype).reshape(bh, n * c, dv))
    return (tuple(x.reshape((n, b, h) + x.shape[2:]) for x in out[:5])
            + (out[5].reshape(n, b, h, dk),))
