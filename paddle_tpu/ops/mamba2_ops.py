"""The Mamba-2 selective scan (state-space duality, Dao & Gu 2024,
arXiv:2405.21060) as a Program op: `mamba2_scan`.

Per head j of H, with a state S in R^{P x N} (float32) that starts at zero,
x_t in R^P, a step dt_t > 0 that depends on the token, one decay rate A_j < 0
a head, and B_t, C_t in R^N that the R = H / G heads of a group share (head j
reads group j // R):

    S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D_j x_t

The simplest member of the family `ops/kda_ops.py` holds: no delta rule, so
no inverse and no levels; what is its own is the step dt (the decay AND the
input's scale), the skip D and the grouped B / C.  ONE lowering, the
chunkwise form at CHUNK = 128 tokens.  With a_t = dt_t A_j, L_t the running
sum of a inside a chunk (<= 0, decreasing) and S the state the chunk enters
with:

    Y  = ((C B^T) o M) (dt x) + exp(L) (C S^T) + D x      M[t, s] = exp(L_t - L_s), s <= t
    S' = exp(L_end) S + ((dt x) exp(L_end - L))^T B

exp is only ever taken of a difference that is <= 0 (clamped where the mask
drops the pair), so a head that forgets at once gives neither inf nor NaN.

Three Pallas kernels (compiled on a TPU, interpreted elsewhere; no flag
chooses), each over the grid (batch, groups, chunks) with the chunks last and
sequential and the R heads of a group in one grid step: C B^T is made once a
group a chunk and read by its R heads, B and C are read where they lie
([B, G, T, N], through the index maps: never a repeat written to memory), and
the heads' chains of dependent products interleave.  The state lives in a
VMEM scratch [R, P, N] float32 from a head's first chunk to its last and
never goes to HBM in the forward:

  `_scan`        the chunk's inside and the carry in one visit -> Y
  `_scan(keep_states)`  the backward's first walk: the state every chunk
                 ENTERED with, [N chunks, B, H, P, N] float32 (one product a
                 visit: the inside is not made)
  `_scan_bwd`    from the last chunk to the first with dS in the scratch: a
                 visit makes the chunk's decays and C B^T again and everything
                 the chunk owes: dx, d dt, d a (a row each, the running sum
                 transposed in the kernel), D's gradient a token, and dB, dC
                 summed over the group's heads in float32.

The backward is the op's own (`jax.custom_vjp`): nothing but the inputs is
kept from the forward (behind an optimization barrier with the result's
gradient, as `kda_ops` does).  Precision: dt, A, a, the running sums, every
exp, the carried state and its gradient are float32 whatever the trunk; the
operands of the products (x, dt x, B, C, the masked C B^T, the state as an
operand) are in X's dtype (bfloat16 under the AMP pass) with float32
accumulation.  T that is no multiple of 128 pads on the right inside the op
(dt = 0, x = 0: the state passes through unchanged).
"""

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register
from . import pallas_kernels as _pk
from .kda_kernels import _bmm, _column, _row
from .pallas_kernels import _note, _sds

CHUNK = 128  # the published chunk_size
_F32 = jnp.float32


def _mm(a, b, dims, dtype):
    """[., .] x [., .] contracted over axes `dims` (a's, b's): operands in
    `dtype`, float32 accumulation (float32 operands at full precision)."""
    return jax.lax.dot_general(
        a.astype(dtype), b.astype(dtype), (((dims[0],), (dims[1],)), ((), ())),
        precision=(jax.lax.Precision.HIGHEST if dtype == _F32 else None),
        preferred_element_type=_F32)


def _heads(x, r):
    """[Q, N] of a group -> [R, Q, N]: one for every head that reads it."""
    return jnp.broadcast_to(x[None], (r,) + x.shape)


def _chunk_decay(dt_ref, a_ref):
    """dt and a = dt A of a chunk's R heads as rows [R, 1, Q] -> (the masks
    t == s and s <= t [1, Q, Q] and the chunk's last row [R, Q, 1]; L, the
    running sum of a, a column [R, Q, 1]; M [R, Q, Q] = exp(L_t - L_s) for
    s <= t, 0 above; L_end [R, 1, 1]; dt a column [R, Q, 1])."""
    t = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK, CHUNK), 1)
    s = jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK, CHUNK), 2)
    eye, seen = t == s, s <= t
    upto = jnp.sum(jnp.where(seen, a_ref[...], 0.0), axis=2, keepdims=True)
    decay = jnp.where(
        seen, jnp.exp(jnp.minimum(upto - _row(upto, eye), 0.0)), 0.0)
    # the chunk's last row by a masked sum: Mosaic refuses a one-row
    # sublane slice
    at_end = jax.lax.broadcasted_iota(jnp.int32, upto.shape, 1) == CHUNK - 1
    last = jnp.sum(jnp.where(at_end, upto, 0.0), axis=1, keepdims=True)
    return (eye, seen, at_end), upto, decay, last, _column(dt_ref[...], eye)


def _scan_kernel(*refs, keep_states):
    from jax.experimental import pallas as pl

    state = refs[-1]  # S [R, P, N] float32
    if keep_states:
        x_ref, dt_ref, a_ref, b_ref, out_ref = refs[:-1]
    else:
        x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, out_ref = refs[:-1]
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        state[...] = jnp.zeros_like(state)

    s = state[...]
    _, upto, decay, last, dt = _chunk_decay(dt_ref, a_ref)
    xf = x_ref[...].astype(_F32)
    xd = xf * dt
    r = xf.shape[0]
    if keep_states:
        out_ref[...] = s
    else:
        inside = _mm(c_ref[...], b_ref[...], (1, 1), dtype)[None] * decay
        y = (_bmm(inside, xd, (2, 1), dtype)
             + jnp.exp(upto) * _bmm(_heads(c_ref[...], r), s, (2, 2), dtype)
             + d_ref[...] * xf)
        out_ref[...] = y.astype(out_ref.dtype)
    state[...] = jnp.exp(last) * s + _bmm(
        xd * jnp.exp(last - upto), _heads(b_ref[...], r), (1, 1), dtype)


def _scan_bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
                     dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                     d_state):
    from jax.experimental import pallas as pl

    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        d_state[...] = jnp.zeros_like(d_state)

    ds = d_state[...]  # the gradient of the state the chunk LEAVES with
    s = s_ref[...]     # the state it entered with
    (eye, seen, at_end), upto, decay, last, dt = _chunk_decay(dt_ref, a_ref)
    xf = x_ref[...].astype(_F32)
    dy = dy_ref[...].astype(_F32)
    xd = xf * dt
    r = xf.shape[0]
    bmat, cmat = b_ref[...], c_ref[...]
    # Y = ((C B^T) o M) (dt x): the masked product, its two operands, and
    # L through M (exp(L_t - L_s): + for the later token, - for the earlier)
    inside = _mm(cmat, bmat, (1, 1), dtype)[None] * decay
    d_inside = _bmm(dy, xd, (2, 2), dtype)
    dxd = _bmm(inside, dy, (1, 1), dtype)
    d_cb = jnp.sum(d_inside * decay, axis=0)
    pairs = d_inside * inside
    d_upto = (jnp.sum(pairs, 2, keepdims=True)
              - _column(jnp.sum(pairs, 1, keepdims=True), eye))
    # exp(L) (C S^T): what the chunk read of the state it entered with
    dy_in = dy * jnp.exp(upto)
    dc_heads = _bmm(dy_in, s, (2, 1), dtype)
    d_upto = d_upto + jnp.sum(dc_heads * cmat.astype(_F32)[None], -1,
                              keepdims=True)
    # S' = exp(L_end) S + ((dt x) exp(L_end - L))^T B
    out = jnp.exp(last - upto)
    z = xd * out
    dz = _bmm(_heads(bmat, r), ds, (2, 2), dtype)
    db_heads = _bmm(z, ds, (2, 1), dtype)
    dxd = dxd + dz * out
    leaving = jnp.sum(dz * z, -1, keepdims=True)
    gamma = jnp.exp(last)
    d_last = (jnp.sum(leaving, 1, keepdims=True) + gamma * jnp.sum(
        jnp.sum(s * ds, 2, keepdims=True), 1, keepdims=True))
    d_upto = d_upto - leaving + jnp.where(at_end, d_last, 0.0)
    d_state[...] = gamma * ds + _bmm(dy_in, _heads(cmat, r), (1, 1), dtype)
    dx_ref[...] = (d_ref[...] * dy + dt * dxd).astype(dx_ref.dtype)
    ddt_ref[...] = _row(jnp.sum(dxd * xf, -1, keepdims=True), eye)
    # the running sum transposed: from the row to the chunk's end
    da_ref[...] = jnp.sum(jnp.where(seen, d_upto, 0.0), axis=1, keepdims=True)
    dd_ref[...] = _row(jnp.sum(dy * xf, -1, keepdims=True), eye)
    dc_ref[...] = (_mm(d_cb, bmat, (1, 0), dtype)
                   + jnp.sum(dc_heads, 0)).astype(dc_ref.dtype)
    db_ref[...] = (_mm(d_cb, cmat, (0, 0), dtype)
                   + jnp.sum(db_heads, 0)).astype(db_ref.dtype)


def _params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_pk._VMEM_LIMIT_BYTES)


def _specs(x, b, reverse=False):
    """(grid, the BlockSpecs of a group's R heads' chunk of a [B, H, T, P]
    array, of a [B, H, N, 1, Q] array of rows, of a group's chunk of a
    [B, G, T, N] array, of the R heads' [H, 1, 1] numbers, of their states
    in [N, B, H, P, N]); `reverse`: from the last chunk to the first."""
    from jax.experimental import pallas as pl

    (bsz, h, t, p), (g, n) = x.shape, b.shape[1::2]
    r, chunks = h // g, t // CHUNK

    def at(l):
        return chunks - 1 - l if reverse else l

    return ((bsz, g, chunks),
            pl.BlockSpec((None, r, CHUNK, p), lambda i, j, l: (i, j, at(l), 0)),
            pl.BlockSpec((None, r, None, 1, CHUNK),
                         lambda i, j, l: (i, j, at(l), 0, 0)),
            pl.BlockSpec((None, None, CHUNK, n),
                         lambda i, j, l: (i, j, at(l), 0)),
            pl.BlockSpec((r, 1, 1), lambda i, j, l: (j, 0, 0)),
            pl.BlockSpec((None, None, r, p, n),
                         lambda i, j, l: (at(l), i, j, 0, 0)))


def _rows(x):
    """[B, H, T] -> [B, H, N, 1, Q] float32: a chunk's numbers on the
    lanes."""
    return x.astype(_F32).reshape(x.shape[:2] + (-1, 1, CHUNK))


def _scan(x, dt, a, b, c, d, keep_states=False):
    """x [B, H, T, P], dt, a [B, H, T], b, c [B, G, T, N], d [H], T in whole
    chunks -> y [B, H, T, P] in x's dtype; with `keep_states` the state
    every chunk entered with, [N chunks, B, H, P, N] float32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bsz, h, t, p), n = x.shape, b.shape[-1]
    grid, tokens, rows, group, number, states = _specs(x, b)
    _note("ssd")
    if keep_states:
        ins, in_specs = (x, _rows(dt), _rows(a), b), [tokens, rows, rows, group]
        out_specs = states
        out_shape = _sds((t // CHUNK, bsz, h, p, n), _F32, x)
    else:
        ins = (x, _rows(dt), _rows(a), b, c, d.astype(_F32).reshape(h, 1, 1))
        in_specs = [tokens, rows, rows, group, group, number]
        out_specs, out_shape = tokens, _sds(x.shape, x.dtype, x)
    return pl.pallas_call(
        functools.partial(_scan_kernel, keep_states=keep_states),
        grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((h // b.shape[1], p, n), _F32)],
        interpret=_pk._interpret(), compiler_params=_params(),
    )(*ins)


def _scan_bwd(x, dt, a, b, c, d, states, dy):
    """`_scan` transposed -> the gradients of x, b, c (in their dtypes), of
    dt and a ([B, H, T] float32) and of d a token ([B, H, T] float32: the
    caller sums it)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    (bsz, h, t, p), n = x.shape, b.shape[-1]
    grid, tokens, rows, group, number, kept = _specs(x, b, reverse=True)
    row_shape = _sds((bsz, h, t // CHUNK, 1, CHUNK), _F32, x)
    _note("ssd")
    dx, ddt, da, dd, db, dc = pl.pallas_call(
        _scan_bwd_kernel,
        grid=grid,
        in_specs=[tokens, rows, rows, group, group, number, kept, tokens],
        out_specs=[tokens, rows, rows, rows, group, group],
        out_shape=[_sds(x.shape, x.dtype, x), row_shape, row_shape, row_shape,
                   _sds(b.shape, b.dtype, x), _sds(c.shape, c.dtype, x)],
        scratch_shapes=[pltpu.VMEM((h // b.shape[1], p, n), _F32)],
        interpret=_pk._interpret(), compiler_params=_params(),
    )(x, _rows(dt), _rows(a), b, c, d.astype(_F32).reshape(h, 1, 1), states,
      dy.astype(x.dtype))
    return (dx, ddt.reshape(bsz, h, t), da.reshape(bsz, h, t),
            dd.reshape(bsz, h, t), db, dc)


def _whole_chunks(x):
    """[B, H or G, T, ...] padded on the right to whole chunks with tokens
    that leave the state as it is (dt = 0, x = 0)."""
    t = x.shape[2]
    return jnp.pad(x, [(0, 0), (0, 0), (0, -t % CHUNK)]
                   + [(0, 0)] * (x.ndim - 3))


@jax.custom_vjp
def ssd_chunked(x, dt, a, b, c, d):
    """x [B, H, T, P], dt (the step, > 0) and a = dt A (the log-decay, <= 0)
    [B, H, T] float32, b, c [B, G, T, N] with G dividing H, d [H] float32
    -> y [B, H, T, P] in x's dtype.  See the module's docstring."""
    t = x.shape[2]
    with jax.named_scope("chunk_scan"):
        y = _scan(*(_whole_chunks(v) for v in (x, dt, a, b, c)), d)
    return y[:, :, :t]


def _ssd_fwd(x, dt, a, b, c, d):
    return ssd_chunked(x, dt, a, b, c, d), (x, dt, a, b, c, d)


def _ssd_bwd(res, dy):
    # the barrier ties the recomputation to the gradient: without it the
    # compiler may find the forward's identical work and keep ITS results
    # alive from the forward to here
    x, dt, a, b, c, d, dy = jax.lax.optimization_barrier(res + (dy,))
    t = x.shape[2]
    ins = tuple(_whole_chunks(v) for v in (x, dt, a, b, c))
    with jax.named_scope("states"):
        states = _scan(*ins, d, keep_states=True)
    with jax.named_scope("chunk_scan"):
        dx, ddt, da, dd, db, dc = _scan_bwd(*ins, d, states,
                                            _whole_chunks(dy))
    grads = (dx, ddt, da, db, dc)
    return tuple(g[:, :, :t].astype(v.dtype) for g, v in zip(grads, res)) + (
        dd.sum((0, 2)).astype(d.dtype),)


ssd_chunked.defvjp(_ssd_fwd, _ssd_bwd)


def mamba2_scan(x, dt, a_head, b, c, d):
    """The op's function: `a_head` [H] float32 (A_j < 0, one a head); dt
    and the log-decay dt A are float32 whatever they come in."""
    dt = dt.astype(_F32)
    return ssd_chunked(x, dt, dt * a_head.astype(_F32)[None, :, None], b, c,
                       d.astype(_F32))


@register("mamba2_scan")
def _mamba2_scan(ctx, ins, attrs):
    """X [B, H, T, P], Dt [B, H, T] (the step, after its softplus), A [H]
    (negative), B, C [B, G, T, N] (G divides H; head j reads group j // (H /
    G)), D [H] -> Out [B, H, T, P] in X's dtype.  See the module's
    docstring."""
    return {"Out": [mamba2_scan(ins["X"][0], ins["Dt"][0], ins["A"][0],
                                ins["B"][0], ins["C"][0], ins["D"][0])]}


from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    register_infer,
    slot_info as _vi,
)
from .kda_ops import _same  # noqa: E402


@register_infer("mamba2_scan", req_ins=("X", "Dt", "A", "B", "C", "D"),
                req_outs=("Out",))
def _mamba2_scan_infer(op, ins):
    x, dt, b, c = (_vi(ins, k) for k in ("X", "Dt", "B", "C"))
    if any(v is None or v.shape is None for v in (x, dt, b, c)):
        return {}
    if len(x.shape) != 4:
        raise InferError("mamba2_scan wants X [B, H, T, P], got %s"
                         % (x.shape,))
    if not _same(x.shape[:3], dt.shape):
        raise InferError("mamba2_scan Dt%s is not X%s's [B, H, T]: one step "
                         "a head a token" % (dt.shape, x.shape))
    if len(b.shape) != 4 or not _same(b.shape, c.shape) or not _same(
            (x.shape[0], x.shape[2]), (b.shape[0], b.shape[2])):
        raise InferError("mamba2_scan wants B and C [B, G, T, N] alike "
                         "beside X%s, got B%s C%s"
                         % (x.shape, b.shape, c.shape))
    if x.shape[1] > 0 and b.shape[1] > 0 and x.shape[1] % b.shape[1]:
        raise InferError("mamba2_scan: B's %d groups do not divide X's %d "
                         "heads" % (b.shape[1], x.shape[1]))
    for name in ("A", "D"):
        v = _vi(ins, name)
        if (v is not None and v.shape is not None and x.shape[1] > 0
                and tuple(v.shape) != (x.shape[1],)):
            raise InferError("mamba2_scan %s%s is not [%d]: one number a "
                             "head" % (name, v.shape, x.shape[1]))
    return {"Out": [VarInfo(x.shape, x.dtype)]}
