"""The gated delta rule as Program ops: `kda_attention` (Kimi Delta
Attention, a decay of every key channel: this docstring) and, at the end
of the module, `gated_delta_attention` (Gated DeltaNet, ONE decay a head
and key heads shared by several value heads: `gdn_chunked`), which runs
the same carry (the same two kernels) around an inside of its own.

A gated delta-rule linear attention with a per-CHANNEL decay (Kimi Linear,
moonshotai; the published `chunk_kda`).  Per head, with S_0 = 0 in
R^{dk x dv} (float32) and q scaled by `scale` (dk^-0.5):

    S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

g [.., T, dk] <= 0 is the log-decay of every key channel, beta [.., T] the
delta rule's step.  ONE lowering, the chunkwise form:

  with u_t = beta_t (v_t - (Diag(exp(g_t)) S_{t-1})^T k_t) the recurrence
  is S_t = Diag(exp(g_t)) S_{t-1} + k_t u_t^T; over a chunk of C = 64
  tokens that enters with the state S, G_t the running sum of g inside the
  chunk and D(t, i) = exp(G_t - G_i) (per channel, <= 1 for i <= t):

    A_kk[t, i] = beta_t sum_c k_t[c] k_i[c] D(t, i)[c]        i <  t
    A_qk[t, i] =        sum_c q_t[c] k_i[c] D(t, i)[c]        i <= t
    (I + A_kk) [U0 | W] = beta [V | K exp(G)]       (the triangular solve)
    U = U0 - W S;   O = (Q exp(G)) S + A_qk U
    S' = Diag(exp(G_C)) S + (K exp(G_C - G))^T U

  `intra` is everything that does not read S: a Pallas kernel
  (ops/kda_kernels.py: compiled on a TPU, interpreted elsewhere; no flag
  chooses), chunk-parallel over the whole length, that reads q, k, v, g
  where they lie and holds a grid step's BLOCK chunks of one head in VMEM
  from the running sum to the six results, written chunks leading as the
  carry walks them.  `carry` is a Pallas kernel too (kda_kernels.carry,
  since PR 50; a `lax.scan` before): its grid walks groups of CARRY_HEADS
  heads and, last and in order, a head's chunks, with S in a VMEM scratch
  from the first chunk to the last, float32 (and transposed, [dv, dk]: the
  chunk's whole decay scales its lanes); a visit makes U, O and S' (three
  products: [W; Q exp(G)] S is one of 128 rows) and writes O's tile
  straight into [B, H, T, dv].  The state never goes to HBM.

The decay never appears as exp(+cumsum).  D(t, i) is needed inside a
product over c, and exp(G_t) exp(-G_i) overflows where a channel forgets
fast (g = -5 a token is exp(+320) over a chunk).  So a chunk is halved,
and its halves again, down to single tokens (six levels): for t in the
later half of a block and i in the earlier one D factors through the
running sum where the later half STARTS, exp(G_t - G_ref) exp(G_ref -
G_i), both <= 1 and their product the true value wherever that is not
itself below the smallest float; t = i needs no decay.  In the kernel a
level is one product of whole chunks decayed by that level's references,
of which the level's pairs are kept; nothing of the size C x C x dk is
ever made.

The backward is the op's own (`jax.custom_vjp`): nothing but the op's
inputs is kept from the forward (behind an optimization barrier with the
result's gradient, so that the compiler cannot merge the recomputation
with the forward's work and keep that alive instead).  It makes the
carry's operands again (kernel 1), walks the chunks forward for the state
every chunk entered with (stacked float32, [N, B H, dv, dk]: its gradient
through the chunk's whole decay sums s . ds elementwise) and what it
wrote (U, a product's operand, in the operands' dtype):
`carry(keep_states=True)`; walks them backwards with the state's gradient
in the scratch (kda_kernels.carry_bwd: a visit reads the chunk's parts,
its entering state, its U and its tile of the result's gradient where
they lie and makes everything the chunk owes, the three products that do
not wait for the carried gradient among them, so a state is read once),
and hands the six operands' gradients to the transposed inside: a second
kernel that makes a chunk's decays and level products once more in VMEM
(the inverse it reads: the backward's call of kernel 1 keeps it) and
transposes the inside by hand (ops/kda_kernels.py says how).  So a step
makes the carry's operands twice and the inside's levels three times;
before PR 46 the inside was some forty XLA fusions a group of 16 chunks,
made three times and differentiated piece by piece by `jax.vjp` (PERF.md
section 6).

The triangular system is solved by inverting I + A_kk block by block
(forward substitution's arithmetic as two products a level, from the same
levels the decayed products come in) and multiplying the right-hand sides
by the inverse: XLA's own triangular solve took 60% of the op's time on a
v5e (PERF.md section 6, PR 45).

Precision: g, beta, the running sums, every exp, the inverse (three
bfloat16 passes a product: float32 to some 2^-17), the carried state, its
gradient and the stacked entering states are float32 whatever the trunk;
the operands of the other products are in
Q's dtype (bfloat16 under the AMP pass) with float32 accumulation.  T that
is no multiple of 64 is padded on the right inside the op (k = 0, beta =
0, g = 0: the state passes through unchanged), and a length of more than
BLOCK chunks to whole grid steps of BLOCK chunks in the same way.
"""

import functools

import jax
import jax.numpy as jnp

from ..core.registry import register
from . import kda_kernels, kernel_tuning

CHUNK = kda_kernels.CHUNK  # the published kernel's chunk
# chunks of one head that a grid step of the two kernels holds: their
# products are batched over the block, so that a level's 64-row product of
# one chunk does not wait on the same chunk's last one.  On a v5e the op
# alone at 6,144 tokens took 15.2 ms forward + backward at 4, 13.7 at 8,
# 13.6 at 16 in the kernels' first form (11.6 at 8 as they stand:
# tools/kda_core_sweep.py, PERF.md section 6, PR 46); 8 keeps the
# transposed kernel's temporaries well inside its VMEM limit.  A longer
# length pads to whole steps of BLOCK (`_padded`): one chunk a step is what
# the batching exists to avoid.  GROUP, the 16 chunks whose forty
# temporaries the jax.numpy inside bounded, went with them: the whole
# length's operands are one call's results
BLOCK = 8
# heads that a grid step of the carry's kernels holds (kda_kernels.carry /
# carry_bwd): a head's chunks are a chain of dependent 64-row products, and
# the heads of a step are the independent chains that fill the waits
# between them.  On a v5e the op alone, forward + backward (device ms of
# the forward walk / of the backward's two walks; tools/kda_core_sweep.py
# --heads-per-step, PERF.md section 6, PR 50): kda_attention at 1 x 32 x
# 6,144 x 128: 9.11 (0.51 / 1.80) at 4, 8.80 (0.38 / 1.59) at 8, 8.73
# (0.34 / 1.58) at 16, 8.71 (0.34 / 1.57) at 32; gated_delta_attention at
# 8,192 tokens: 9.31 (0.74 / 2.42), 8.80 (0.56 / 2.12), 8.77 (0.53 /
# 2.10), 8.76 (0.53 / 2.09).  16: as fast as 32 to the hundredth, half
# its blocks in VMEM (the reverse walk holds ~8 MB at 16), and two groups
# of the cells' 32 heads for a chip with two cores.  A head count it does
# not divide runs at its largest divisor up to it (`_carry_heads`)
CARRY_HEADS = 16
_F32 = jnp.float32


def _block(t):
    """Chunks a grid step at t tokens."""
    return min(BLOCK, -(-t // CHUNK))


def _padded(t):
    """t in whole grid steps: one step of whole chunks up to BLOCK of
    them, steps of BLOCK chunks beyond."""
    step = _block(t) * CHUNK
    return -(-t // step) * step


def _whole_chunks(x, t):
    """[B, H, T, ...] padded on the right to whole grid steps with tokens
    that leave the state as it is."""
    if _padded(t) == t:
        return x
    return jnp.pad(x, [(0, 0), (0, 0), (0, _padded(t) - t)]
                   + [(0, 0)] * (x.ndim - 3))


def _intra(ins, scale, keep_solve=False):
    """Everything the chunks compute without the state, chunks leading;
    `ins`: the five inputs in whole chunks."""
    with jax.named_scope("intra"):
        return kda_kernels.intra(*ins, scale, _block(ins[0].shape[2]),
                                 keep_solve)


def _carry_heads(heads):
    """Heads a grid step of the carry's kernels holds: the largest divisor
    of the B H heads there are that is no more than CARRY_HEADS."""
    return max(d for d in range(1, min(CARRY_HEADS, heads) + 1)
               if heads % d == 0)


def _carry_forward(parts, v, t):
    """The walk over the chunks that carries S (kda_kernels.carry):
    `parts` (an inside's six results, chunks leading) -> o [B, H, T, dv] in
    v's dtype.  Both members of the family run it: the chunk's whole decay
    is [B, H, dk] a chunk under a per-channel decay, [B, H, 1] under one a
    head."""
    with jax.named_scope("carry"):
        o = kda_kernels.carry(parts, v.dtype,
                              _carry_heads(v.shape[0] * v.shape[1]))
    return o[:, :, :t]


def _carry_backward(parts, v, do):
    """The carry transposed: `parts` as above and the result's gradient
    [B, H, T, dv] -> the six parts' gradients (the last, the chunk's whole
    decay's, [N, B, H, dk]: a decay of one number a head sums it).  Two
    walks: forward for the state every chunk entered with (float32) and
    what it wrote, backwards with the state's gradient as the carry."""
    heads = _carry_heads(v.shape[0] * v.shape[1])
    with jax.named_scope("carry"):
        states, u = kda_kernels.carry(parts, v.dtype, heads,
                                      keep_states=True)
        return kda_kernels.carry_bwd(
            parts, states, u, _whole_chunks(do, do.shape[2]), heads)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def kda_chunked(q, k, v, g, beta, scale):
    """q, k, g [B, H, T, dk], v [B, H, T, dv], beta [B, H, T] -> o
    [B, H, T, dv] in v's dtype."""
    t = q.shape[2]
    parts = _intra(tuple(_whole_chunks(x, t) for x in (q, k, v, g, beta)),
                   scale)
    return _carry_forward(parts, v, t)


def _kda_fwd(q, k, v, g, beta, scale):
    return kda_chunked(q, k, v, g, beta, scale), (q, k, v, g, beta)


def _kda_bwd(scale, res, do):
    # the barrier ties the recomputation to the gradient: without it the
    # compiler may find the forward's identical work and keep ITS results
    # alive from the forward to here
    q, k, v, g, beta, do = jax.lax.optimization_barrier(res + (do,))
    t = q.shape[2]
    ins = tuple(_whole_chunks(x, t) for x in (q, k, v, g, beta))
    parts = _intra(ins, scale, keep_solve=True)
    parts, solve = parts[:6], parts[6]
    d_parts = _carry_backward(parts, v, do)
    with jax.named_scope("intra"):
        grads = kda_kernels.intra_bwd(*ins, solve, d_parts, scale,
                                      _block(ins[0].shape[2]))
    return tuple(d[:, :, :t].astype(x.dtype) for d, x in zip(grads, res))


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
        q.shape[2], _padded(q.shape[2]), CHUNK, _block(q.shape[2]),
        _carry_heads(v.shape[0] * v.shape[1]))
    return {"Out": [kda_chunked(q, k, v, g, beta, scale)]}


# ---------------------------------------------------------------------------
# the family's second member: ONE decay a head (Gated DeltaNet)
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def gdn_chunked(q, k, v, g, beta, scale):
    """The gated delta rule with a scalar decay a head (Gated DeltaNet,
    Yang et al. 2024, arXiv:2412.06464; Qwen3-Next's linear layers): q, k
    [B, Hk, T, dk], v [B, Hv, T, dv], g (the log-decay, <= 0) and beta
    [B, Hv, T] float32 -> o [B, Hv, T, dv] in v's dtype.  Value head j
    reads key head j // (Hv / Hk).

        S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t scale

    The carry is KDA's, code and all (`_carry_forward` /
    `_carry_backward`, the chunk's whole decay one number a head); the
    chunk's inside is not: D(t, i) = exp(G_t - G_i) is a [C, C] matrix
    that multiplies Q K^T and K K^T after ONE product (kda_kernels.
    gdn_intra), exp only ever of a difference <= 0, no level and no
    reference, and the decay is never broadcast to the channels."""
    t = v.shape[2]
    parts = _gdn_intra(tuple(_whole_chunks(x, t) for x in (q, k, v, g, beta)),
                       scale)
    return _carry_forward(parts, v, t)


def _gdn_intra(ins, scale, keep_solve=False):
    with jax.named_scope("intra"):
        return kda_kernels.gdn_intra(*ins, scale, _block(ins[2].shape[2]),
                                     keep_solve)


def _gdn_fwd(q, k, v, g, beta, scale):
    return gdn_chunked(q, k, v, g, beta, scale), (q, k, v, g, beta)


def _gdn_bwd(scale, res, do):
    # as `_kda_bwd`: nothing but the inputs is kept, and the barrier ties
    # the recomputation to the gradient
    q, k, v, g, beta, do = jax.lax.optimization_barrier(res + (do,))
    t = v.shape[2]
    ins = tuple(_whole_chunks(x, t) for x in (q, k, v, g, beta))
    parts = _gdn_intra(ins, scale, keep_solve=True)
    parts, solve = parts[:6], parts[6]
    d_parts = _carry_backward(parts, v, do)
    d_parts = d_parts[:5] + (d_parts[5].sum(-1, keepdims=True),)
    with jax.named_scope("intra"):
        dq, dk, dv, dg, dbeta = kda_kernels.gdn_intra_bwd(
            *ins, solve, d_parts, scale, _block(ins[2].shape[2]))

        def readers(d):  # a key head's gradient: the sum over its readers
            b, hk = q.shape[:2]
            return d.reshape(b, hk, -1, *d.shape[2:]).astype(_F32).sum(2)

        grads = (readers(dq), readers(dk), dv, dg, dbeta)
    return tuple(d[:, :, :t].astype(x.dtype) for d, x in zip(grads, res))


gdn_chunked.defvjp(_gdn_fwd, _gdn_bwd)


@register("gated_delta_attention")
def _gated_delta_attention(ctx, ins, attrs):
    """Q, K [B, Hk, T, dk], V [B, Hv, T, dv], G, Beta [B, Hv, T] -> Out
    [B, Hv, T, dv] in V's dtype; Hk divides Hv and value head j reads key
    head j // (Hv / Hk).  `scale` multiplies q (dk^-0.5 where not given).
    See `gdn_chunked`."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    g, beta = ins["G"][0], ins["Beta"][0]
    scale = attrs.get("scale")
    scale = float(q.shape[-1]) ** -0.5 if scale is None else float(scale)
    kernel_tuning.note_kda_chunks(
        v.shape[2], _padded(v.shape[2]), CHUNK, _block(v.shape[2]),
        _carry_heads(v.shape[0] * v.shape[1]), decay="head")
    return {"Out": [gdn_chunked(q, k, v, g, beta, scale)]}


from ..analysis.infer import (  # noqa: E402
    InferError,
    VarInfo,
    register_infer,
    slot_info as _vi,
)


def _same(a, b):
    """Two declared shapes agree (a negative extent agrees with any)."""
    return len(a) == len(b) and all(
        x == y or x < 0 or y < 0 for x, y in zip(a, b))


@register_infer("kda_attention", req_ins=("Q", "K", "V", "G", "Beta"),
                req_outs=("Out",))
def _kda_attention_infer(op, ins):
    q, k, v = _vi(ins, "Q"), _vi(ins, "K"), _vi(ins, "V")
    g, beta = _vi(ins, "G"), _vi(ins, "Beta")
    if any(x is None or x.shape is None for x in (q, k, v, g, beta)):
        return {}
    if len(q.shape) != 4 or not _same(q.shape, k.shape) \
            or not _same(q.shape, g.shape):
        raise InferError("kda_attention wants Q, K and G [B, H, T, dk] "
                         "alike, got Q%s K%s G%s"
                         % (q.shape, k.shape, g.shape))
    if len(v.shape) != 4 or not _same(q.shape[:3], v.shape[:3]):
        raise InferError("kda_attention V%s is not [B, H, T, dv] beside Q%s"
                         % (v.shape, q.shape))
    if not _same(q.shape[:3], beta.shape):
        raise InferError("kda_attention Beta%s is not Q%s's [B, H, T]"
                         % (beta.shape, q.shape))
    return {"Out": [VarInfo(v.shape, v.dtype)]}


@register_infer("gated_delta_attention",
                req_ins=("Q", "K", "V", "G", "Beta"), req_outs=("Out",))
def _gated_delta_attention_infer(op, ins):
    q, k, v = _vi(ins, "Q"), _vi(ins, "K"), _vi(ins, "V")
    g, beta = _vi(ins, "G"), _vi(ins, "Beta")
    if any(x is None or x.shape is None for x in (q, k, v, g, beta)):
        return {}
    if len(q.shape) != 4 or not _same(q.shape, k.shape):
        raise InferError("gated_delta_attention wants Q and K [B, Hk, T, dk] "
                         "alike, got Q%s K%s" % (q.shape, k.shape))
    if len(v.shape) != 4 or not _same(
            (q.shape[0], q.shape[2]), (v.shape[0], v.shape[2])):
        raise InferError("gated_delta_attention V%s is not [B, Hv, T, dv] "
                         "beside Q%s" % (v.shape, q.shape))
    if q.shape[1] > 0 and v.shape[1] > 0 and v.shape[1] % q.shape[1]:
        raise InferError("gated_delta_attention: Q's %d key heads do not "
                         "divide V's %d value heads"
                         % (q.shape[1], v.shape[1]))
    for name, x in (("G", g), ("Beta", beta)):
        if not _same(v.shape[:3], x.shape):
            raise InferError("gated_delta_attention %s%s is not V%s's "
                             "[B, Hv, T]: one number a head a token"
                             % (name, x.shape, v.shape))
    return {"Out": [VarInfo(v.shape, v.dtype)]}
