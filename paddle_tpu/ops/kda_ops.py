"""Kimi Delta Attention as a Program op: `kda_attention`.

A gated delta-rule linear attention with a per-CHANNEL decay (Kimi Linear,
moonshotai; the published `chunk_kda`).  Per head, with S_0 = 0 in
R^{dk x dv} (float32) and q scaled by `scale` (dk^-0.5):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

g [.., T, dk] <= 0 is the log-decay of every key channel, beta [.., T] the
delta rule's step.  ONE lowering, the chunkwise form in jax.numpy / lax:

  with u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) the recurrence
  is S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T; over a chunk of C = 64
  tokens that enters with the state S, G_t the running sum of g inside the
  chunk and D(t, i) = exp(G_t - G_i) (per channel, <= 1 for i <= t):

    A_kk[t, i] = beta_t sum_c k_t[c] k_i[c] D(t, i)[c]        i <  t
    A_qk[t, i] =        sum_c q_t[c] k_i[c] D(t, i)[c]        i <= t
    (I + A_kk) [U0 | W] = beta [V | K exp(G)]       (the triangular solve)
    U = U0 - W S;   O = (Q exp(G)) S + A_qk U
    S' = Diag(exp(G_C)) S + (K exp(G_C - G))^T U

  `intra` is everything that does not read S, computed for a GROUP of 16
  chunks at once; `carry` is a `lax.scan` over the group's chunks that
  carries S [B, H, dk, dv] in float32 (three products a chunk); an outer
  `lax.scan` walks the T / (16 C) groups, so the op's temporaries are a
  group's, whatever T.

The decay never appears as exp(+cumsum).  D(t, i) is needed inside a
product over c, and exp(G_t) exp(-G_i) overflows where a channel forgets
fast (g = -5 a token is exp(+320) over a chunk).  So a chunk is halved,
and its halves again, down to single tokens (six levels): for t in the
later half of a block and i in the earlier one D factors through the
running sum where the later half STARTS, exp(G_t - G_ref) exp(G_ref -
G_i), both <= 1 and their product the true value wherever that is not
itself below the smallest float; t = i needs no decay.  Every level is one
batched product; nothing of the size C x C x dk is ever made.

The backward is the op's own (`jax.custom_vjp`): nothing but the op's
inputs is kept from the forward (behind an optimization barrier with the
result's gradient, so that the compiler cannot merge the recomputation
with the forward's work and keep that alive instead).  A first walk over
the groups makes the state each group enters with again (B x H x dk x dv
float32 a group); a second walk, backwards, makes a group's inside again,
runs its carry forward for the state every chunk entered with, walks its
chunks backwards with the state's gradient as the carry, and hands what
that gives to the transposed inside.

The triangular system is solved by inverting I + A_kk block by block
(`_unit_lower_inverse`: forward substitution's arithmetic as two batched
products a level, from the same levels the decayed products come in) and
multiplying the right-hand sides by the inverse: XLA's own triangular
solve took 60% of the op's time on a v5e (PERF.md section 6, PR 45).

Precision: g, beta, the running sums, every exp, the inverse (three
bfloat16 passes a product: float32 to some 2^-17) and the carried state
are float32 whatever the trunk; the operands of the other products are in
Q's dtype (bfloat16 under the AMP pass) with float32 accumulation.  T that
is no multiple of 64 is padded on the right inside the op (k = 0, beta =
0, g = 0: the state passes through unchanged).
"""

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register
from . import kernel_tuning

CHUNK = 64  # the published kernel's chunk
# chunks whose inside is computed at once: what bounds the op's temporaries
# (the inside's operands and, in the backward, what its transposition keeps
# are some forty arrays the size of a group's q).  On a v5e the op alone is
# faster at 4 (35 ms forward + backward a layer at 6,144 tokens against 45
# at 8: tools/kda_core_sweep.py, PERF.md section 7); 16 is the one of 2, 3,
# 4, 6, 8, 12, 16 at which the compiler counts the kimi_linear_48b_a3b_train
# step at or under the cell's 15.0 GiB (PERF.md section 4)
GROUP = 16

_F32 = jnp.float32


def _mm(eq, a, b, dtype):
    """einsum with operands in `dtype`, accumulated in float32."""
    return jnp.einsum(eq, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=_F32)


def _halves(x, m):
    """[.., C, d] -> [.., C / 2m, 2, m, d]: blocks of 2m tokens, halved."""
    return x.reshape(x.shape[:-2] + (-1, 2, m, x.shape[-1]))


def _stack_lower(done, below):
    """Blocks of m [.., 2n, m, m] and what lies below the diagonal between
    each pair [.., n, m, m] -> blocks of 2m [.., n, 2m, 2m], zeros above."""
    done = done.reshape(below.shape[:-2] + (2,) + done.shape[-2:])
    return jnp.concatenate([
        jnp.concatenate([done[..., 0, :, :], jnp.zeros_like(below)], -1),
        jnp.concatenate([below, done[..., 1, :, :]], -1)], -2)


def _pair_scores(q, k, gsum, dtype):
    """The decayed products of one chunk, q, k, gsum [.., C, dk] float32, C
    a power of two: -> (A [.., C, C] with A[t, i] = sum_c q[t, c] k[i, c]
    exp(gsum[t, c] - gsum[i, c]) for i <= t and 0 above the diagonal; for
    each level m = 1, 2, .. C / 2 the same sums with k in q's place for t
    in the later half and i in the earlier half of every block of 2m
    tokens, [.., C / 2m, m, m]).  No exponent is positive: the chunk is
    halved down to single tokens, and at every level the pairs of a later
    and an earlier half go through the running sum where the later half
    starts."""
    c = k.shape[-2]
    a_qk = (q * k).sum(-1)[..., None, None]  # t = i: no decay
    kk, m = [], 1
    while m < c:
        gs, ks = _halves(gsum, m), _halves(k, m)
        ref = gs[..., 0, m - 1:, :]   # what the earlier half's last token left
        earlier = ks[..., 0, :, :] * jnp.exp(ref - gs[..., 0, :, :])
        later = jnp.exp(gs[..., 1, :, :] - ref)
        here = jnp.concatenate([_halves(q, m)[..., 1, :, :] * later,
                                ks[..., 1, :, :] * later], -2)
        below = _mm("...tc,...ic->...ti", here, earlier, dtype)
        a_qk = _stack_lower(a_qk, below[..., :m, :])
        kk.append(below[..., m:, :])
        m *= 2
    return a_qk[..., 0, :, :], kk


def _unit_lower_inverse(levels):
    """(I + A)^-1 [.., C, C] float32 for A strictly lower triangular, given
    by `levels`: for m = 1, 2, .. C / 2 the blocks of A below the diagonal
    between the halves of every block of 2m tokens, [.., C / 2m, m, m].
    Blocks of one token are their own inverse, and two inverted blocks T1,
    T2 with L below the diagonal between them make [[T1, 0], [-T2 L T1,
    T2]]: two batched products a level at three bfloat16 passes (float32 to
    some 2^-17: the published kernel's `ieee` products, at half the six
    passes' time).  Forward substitution's arithmetic, on the MXU."""
    hi = jax.lax.Precision.HIGH
    n = 2 * levels[0].shape[-3]
    inv = jnp.ones(levels[0].shape[:-3] + (n, 1, 1), _F32)
    for below in levels:
        done = inv.reshape(below.shape[:-2] + (2,) + inv.shape[-2:])
        corner = -jnp.einsum(
            "...ij,...jk->...ik",
            jnp.einsum("...ij,...jk->...ik", done[..., 1, :, :], below,
                       precision=hi),
            done[..., 0, :, :], precision=hi)
        inv = _stack_lower(inv, corner)
    return inv[..., 0, :, :]


def _intra(q, k, v, g, beta, scale):
    """What a chunk computes without the incoming state, for arrays
    [.., C, d] (beta [.., C]) of any leading shape: -> (W, U0, A_qk,
    Q exp(G), K exp(G_C - G), exp(G_C)); the operands of the carry's
    products in q's dtype, U0 and the chunk's whole decay float32."""
    dtype = q.dtype
    with jax.named_scope("intra"):
        # the running sum inside the chunk, as a product with a triangle
        # of ones (a reduce-window took 0.3 ms a layer on a v5e)
        c = g.shape[-2]
        gsum = jnp.einsum("ti,...ic->...tc", jnp.tril(jnp.ones((c, c), _F32)),
                          g.astype(_F32),
                          precision=jax.lax.Precision.HIGHEST)
        qf, kf = q.astype(_F32) * scale, k.astype(_F32)
        beta = beta.astype(_F32)[..., None]
        a_qk, kk = _pair_scores(qf, kf, gsum, dtype)
        # (I + A_kk) [U0 | W] = beta [V | K exp(G)], A_kk = beta_t (k.k
        # decayed) strictly below the diagonal: level by level
        solve = _unit_lower_inverse([
            _halves(beta, below.shape[-1])[..., 1, :, :] * below
            for below in kk])
        into = jnp.exp(gsum)
        last = gsum[..., -1:, :]
        return (_mm("...ti,...ic->...tc", solve, beta * kf * into,
                    dtype).astype(dtype),
                _mm("...ti,...iv->...tv", solve, beta * v.astype(_F32),
                    dtype),
                a_qk.astype(dtype), (qf * into).astype(dtype),
                (kf * jnp.exp(last - gsum)).astype(dtype),
                jnp.exp(last[..., 0, :]))


def _new_values(w, u0, s, dtype):
    """U = U0 - W S: what the chunk's tokens write, given the state."""
    return u0 - _mm("...tc,...cv->...tv", w, s, dtype)


def _next_state(s, u, kd, gamma, dtype):
    return gamma[..., None] * s + _mm("...tc,...tv->...cv", kd, u, dtype)


def _padded(t):
    return -(-t // CHUNK) * CHUNK


def _groups(n):
    """Chunks a group: the largest divisor of the n chunks up to GROUP."""
    return max(g for g in range(1, min(GROUP, n) + 1) if n % g == 0)


def _grouped(x, t):
    """[B, H, T, ...] -> [n / G, G, B, H, C, ...]: padded on the right to
    whole chunks, groups of chunks leading, for the two scans."""
    n = _padded(t) // CHUNK
    if n * CHUNK > t:
        x = jnp.pad(x, [(0, 0), (0, 0), (0, n * CHUNK - t)]
                    + [(0, 0)] * (x.ndim - 3))
    x = jnp.moveaxis(x.reshape(x.shape[:2] + (n, CHUNK) + x.shape[3:]), 2, 0)
    return x.reshape((-1, _groups(n)) + x.shape[1:])


def _ungrouped(x, t):
    """[n / G, G, B, H, C, ...] -> [B, H, T, ...]."""
    x = jnp.moveaxis(x.reshape((-1,) + x.shape[2:]), 0, 2)
    return x.reshape(x.shape[:2] + (-1,) + x.shape[4:])[:, :, :t]


def _state0(q, v):
    return jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), _F32)


def _states(s, parts, dtype):
    """The carry alone over one group's chunks: -> (the state the group
    leaves, the state each of its chunks entered with)."""
    def step(s, xs):
        w, u0, _, _, kd, gamma = xs
        return _next_state(s, _new_values(w, u0, s, dtype), kd, gamma,
                           dtype), s

    return jax.lax.scan(step, s, parts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunked(q, k, v, g, beta, scale):
    """q, k, g [B, H, T, dk], v [B, H, T, dv], beta [B, H, T] -> o
    [B, H, T, dv] in v's dtype."""
    t, dtype = q.shape[2], q.dtype

    def step(s, xs):
        w, u0, a_qk, qg, kd, gamma = xs
        u = _new_values(w, u0, s, dtype)
        o = (_mm("...tc,...cv->...tv", qg, s, dtype)
             + _mm("...ti,...iv->...tv", a_qk, u, dtype))
        return _next_state(s, u, kd, gamma, dtype), o.astype(v.dtype)

    def group(s, xs):
        parts = _intra(*xs, scale)
        with jax.named_scope("carry"):
            return jax.lax.scan(step, s, parts)

    _, o = jax.lax.scan(group, _state0(q, v),
                        tuple(_grouped(x, t) for x in (q, k, v, g, beta)))
    return _ungrouped(o, t)


def _kda_fwd(q, k, v, g, beta, scale):
    return kda_chunked(q, k, v, g, beta, scale), (q, k, v, g, beta)


def _kda_bwd(scale, res, do):
    # the barrier ties the recomputation to the gradient: without it the
    # compiler may find the forward's identical work and keep ITS results
    # alive from the forward to here
    q, k, v, g, beta, do = jax.lax.optimization_barrier(res + (do,))
    t, dtype = q.shape[2], q.dtype
    xs = tuple(_grouped(x, t) for x in (q, k, v, g, beta))

    def entering(s, xs):
        parts = _intra(*xs, scale)
        with jax.named_scope("carry"):
            return _states(s, parts, dtype)[0], s

    def backward(ds, xs):
        (w, u0, a_qk, qg, kd, gamma), s, d_o = xs
        u = _new_values(w, u0, s, dtype)
        du = (_mm("...ti,...tv->...iv", a_qk, d_o, dtype)
              + _mm("...tc,...cv->...tv", kd, ds, dtype))
        d_parts = ((-_mm("...tv,...cv->...tc", du, s, dtype)).astype(dtype),
                   du,
                   _mm("...tv,...iv->...ti", d_o, u, dtype).astype(dtype),
                   _mm("...tv,...cv->...tc", d_o, s, dtype).astype(dtype),
                   _mm("...tv,...cv->...tc", u, ds, dtype).astype(dtype),
                   (s * ds).sum(-1))
        ds = (_mm("...tc,...tv->...cv", qg, d_o, dtype)
              + gamma[..., None] * ds
              - _mm("...tc,...tv->...cv", w, du, dtype))
        return ds, d_parts

    def group(ds, xs):
        xs, s, d_o = xs
        parts, intra_vjp = jax.vjp(lambda *a: _intra(*a, scale), *xs)
        with jax.named_scope("carry"):
            _, states = _states(s, parts, dtype)
            ds, d_parts = jax.lax.scan(backward, ds, (parts, states, d_o),
                                       reverse=True)
        return ds, intra_vjp(d_parts)

    s0 = _state0(q, v)
    _, starts = jax.lax.scan(entering, s0, xs)
    _, grads = jax.lax.scan(group, jnp.zeros_like(s0),
                            (xs, starts, _grouped(do, t)), reverse=True)
    return tuple(_ungrouped(d, t).astype(x.dtype)
                 for d, x in zip(grads, res))


kda_chunked.defvjp(_kda_fwd, _kda_bwd)


@register("kda_attention")
def _kda_attention(ctx, ins, attrs):
    """Q, K, G [B, H, T, dk], V [B, H, T, dv], Beta [B, H, T] -> Out
    [B, H, T, dv] in V's dtype.  `scale` multiplies q (dk^-0.5 where not
    given).  See the module's docstring."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    g, beta = ins["G"][0], ins["Beta"][0]
    scale = attrs.get("scale")
    scale = float(q.shape[-1]) ** -0.5 if scale is None else float(scale)
    kernel_tuning.note_kda_chunks(
        q.shape[2], _padded(q.shape[2]), CHUNK,
        _groups(_padded(q.shape[2]) // CHUNK))
    return {"Out": [kda_chunked(q, k, v, g, beta, scale)]}


from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    register_infer,
    slot_info as _vi,
)


@register_infer("kda_attention", req_ins=("Q", "K", "V", "G", "Beta"),
                req_outs=("Out",))
def _kda_attention_infer(op, ins):
    q, k, v = _vi(ins, "Q"), _vi(ins, "K"), _vi(ins, "V")
    g, beta = _vi(ins, "G"), _vi(ins, "Beta")
    if any(x is None or x.shape is None for x in (q, k, v, g, beta)):
        return {}

    def same(a, b):
        return len(a) == len(b) and all(
            x == y or x < 0 or y < 0 for x, y in zip(a, b))

    if len(q.shape) != 4 or not same(q.shape, k.shape) \
            or not same(q.shape, g.shape):
        raise InferError("kda_attention wants Q, K and G [B, H, T, dk] "
                         "alike, got Q%s K%s G%s"
                         % (q.shape, k.shape, g.shape))
    if len(v.shape) != 4 or not same(q.shape[:3], v.shape[:3]):
        raise InferError("kda_attention V%s is not [B, H, T, dv] beside Q%s"
                         % (v.shape, q.shape))
    if not same(q.shape[:3], beta.shape):
        raise InferError("kda_attention Beta%s is not Q%s's [B, H, T]"
                         % (beta.shape, q.shape))
    return {"Out": [VarInfo(v.shape, v.dtype)]}
