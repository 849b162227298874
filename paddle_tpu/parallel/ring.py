"""Ring attention — sequence/context parallelism over a mesh axis.

The reference has no long-context story (SURVEY.md §5.7 'Absent'); this is
green-field TPU design: K/V blocks rotate around the `sp` axis ring via
ppermute (one hop per step, riding ICI) while each device holds its local Q
chunk and maintains a flash-style running logsumexp — memory O(T_local),
compute overlapped with the rotation by XLA's async collective scheduling.

The ring is a `lax.scan` (HLO size is O(1) in ring size, unlike an
unrolled loop), and with use_flash=True each chunk-vs-chunk piece runs
through the Pallas flash kernel (flash_attention_piece) — so neither the
per-chunk [T_local, T_local] score matrix nor the fwd residuals ever hit
HBM; by default the pieces are dense XLA.
Differentiable end-to-end (scan + ppermute + custom-vjp flash piece).

Use `ring_attention(...)` inside shard_map (see `ring_attention_sharded`
for the wrapped convenience entry).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["ring_attention", "ring_attention_sharded"]

from ..ops.pallas_kernels import NEG_INF as _NEG


def _dense_piece(q, k, v, scale, bias=None):
    """One q-chunk x k-chunk attention piece -> (o_norm, lse), f32 lse.
    q:[B,H,Tq,D] k,v:[B,H,Tk,D]; bias broadcastable to [B,H,Tq,Tk]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)  # [B,H,Tq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)) / safe_l[..., None]
    return o, m + jnp.log(safe_l)


def _flash_piece_bhtd(q, k, v, causal, scale, window=0):
    """Pallas flash piece over [B,H,T,D] (kernel wants [BH,T,D])."""
    from ..ops.pallas_kernels import flash_attention_piece

    B, H, T, D = q.shape
    Tk = k.shape[2]
    blk = 128 if (T % 128 == 0 and Tk % 128 == 0) else 8
    o, lse = flash_attention_piece(
        q.reshape(B * H, T, D), k.reshape(B * H, Tk, D),
        v.reshape(B * H, Tk, D), causal, scale, blk, blk, window)
    return (o.astype(jnp.float32).reshape(B, H, T, D),
            lse.reshape(B, H, T))


def ring_attention(q, k, v, axis_name, causal=False, scale=None,
                   use_flash=False, window=0):
    """Per-shard attention with K/V ring rotation.

    q, k, v: local chunks [B, H, T_local, D]; global sequence is the
    concatenation over the `axis_name` ring in axis-index order.
    Returns the local output chunk [B, H, T_local, D].

    window > 0 (requires causal): GLOBAL sliding-window attention across
    the ring — each query sees the last `window` global positions, and
    chunks entirely outside every local query's window are skipped
    whole, so per-device compute scales with the window, not the global
    sequence.  (Windowed pieces run on the dense chunk path: the banded
    mask depends on the traced ring offset.)
    """
    window = int(window)
    if window < 0:
        raise ValueError("ring_attention: window must be >= 0")
    if window and not causal:
        raise ValueError("ring_attention: window requires causal=True")
    n = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    t_local = q.shape[2]
    if scale is None:
        scale = q.shape[-1] ** -0.5
    scale = float(scale)
    flash = use_flash and t_local >= 8 and t_local % 8 == 0
    q_pos = my * t_local + jnp.arange(t_local)  # global positions of local q
    # device-varying types for anything a cond/scan branch must produce
    from .mesh import pcast_varying, vma_of

    vma = tuple(vma_of(q) | {axis_name})

    def skip_piece():
        """A chunk contributing nothing: lse = -1e30 washes out of the
        merge."""
        return (pcast_varying(jnp.zeros(q.shape, jnp.float32), vma),
                pcast_varying(jnp.full(q.shape[:-1], _NEG, jnp.float32),
                              vma))

    def piece(k_blk, v_blk, src):
        """(o, lse) of local q vs the chunk originating at rank `src`."""
        if not causal:
            if flash:
                return _flash_piece_bhtd(q, k_blk, v_blk, False, scale)
            return _dense_piece(q, k_blk, v_blk, scale)
        if flash:
            # src == my: the diagonal chunk — causal within, and the ring
            # offsets cancel so the kernel's LOCAL window mask is exact;
            # src < my: visible (band-masked off-diagonal when windowed —
            # dense, since that mask depends on the traced offset);
            # src > my: fully masked (skipped)
            def offdiag():
                if not window:
                    return _flash_piece_bhtd(q, k_blk, v_blk, False, scale)
                k_pos_od = src * t_local + jnp.arange(t_local)
                m = ((q_pos[:, None] >= k_pos_od[None, :])
                     & (q_pos[:, None] - k_pos_od[None, :] < window))
                bias_od = jnp.where(m, 0.0, _NEG).astype(
                    jnp.float32)[None, None]
                contributes = (my - src - 1) * t_local + 1 < window
                return jax.lax.cond(
                    contributes,
                    lambda: _dense_piece(q, k_blk, v_blk, scale, bias_od),
                    skip_piece,
                )
            return jax.lax.cond(
                src == my,
                lambda: _flash_piece_bhtd(q, k_blk, v_blk, True, scale,
                                          window),
                lambda: jax.lax.cond(src < my, offdiag, skip_piece),
            )
        k_pos = src * t_local + jnp.arange(t_local)
        mask = q_pos[:, None] >= k_pos[None, :]
        if window:
            mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
        bias = jnp.where(mask, 0.0, _NEG).astype(jnp.float32)[None, None]
        if window:
            # skip chunks entirely older than every local query's window:
            # the closest (q, k) pair of chunks (my, src<my) sits
            # (my-src-1)*T_local + 1 positions apart
            contributes = (src == my) | (
                (src < my) & ((my - src - 1) * t_local + 1 < window))
            return jax.lax.cond(
                contributes,
                lambda: _dense_piece(q, k_blk, v_blk, scale, bias),
                skip_piece,
            )
        return _dense_piece(q, k_blk, v_blk, scale, bias)

    perm = [(j, (j + 1) % n) for j in range(n)]

    def step(carry, i):
        k_blk, v_blk, o_acc, lse_acc = carry
        src = (my - i) % n  # which rank's chunk we currently hold
        o_blk, lse_blk = piece(k_blk, v_blk, src)
        lse_new = jnp.logaddexp(lse_acc, lse_blk)
        o_new = (o_acc * jnp.exp(lse_acc - lse_new)[..., None]
                 + o_blk * jnp.exp(lse_blk - lse_new)[..., None])
        # rotate k/v to the next rank (ring over ICI)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_blk, axis_name, perm)
        return (k_nxt, v_nxt, o_new, lse_new), None

    # mark the accumulators device-varying over every axis the inputs vary
    # on (the ring axis, plus e.g. a dp axis on a composite mesh) so the
    # scan carry type matches the body output under shard_map
    o0 = pcast_varying(jnp.zeros(q.shape, jnp.float32), vma)
    lse0 = pcast_varying(
        jnp.full(q.shape[:-1], -jnp.inf, jnp.float32), vma)
    (_, _, o_f, _), _ = jax.lax.scan(
        step, (k, v, o0, lse0), jnp.arange(n))
    return o_f.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                           use_flash=False, window=0):
    """Convenience wrapper: shard q/k/v over `axis_name` on the time dim and
    run ring_attention under shard_map.  q,k,v: [B, H, T, D] global."""
    from .mesh import shard_map

    spec = P(None, None, axis_name, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def inner(ql, kl, vl):
        return ring_attention(ql, kl, vl, axis_name, causal=causal,
                              use_flash=use_flash, window=window)

    return inner(q, k, v)
