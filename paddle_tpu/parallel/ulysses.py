"""All-to-all (Ulysses-style) sequence parallelism — the second
long-context strategy next to ring attention (parallel/ring.py).

Where ring attention keeps Q resident and rotates K/V blocks around the
`sp` axis (T/n memory, n ppermute hops), the all-to-all form re-shards
once: tokens arrive sharded on the TIME axis, one all_to_all turns that
into a HEAD-sharded layout so every device runs ordinary full-sequence
attention for H/n heads, and a second all_to_all restores time sharding.
Two collectives total regardless of sequence length — the better trade
when heads divide the axis and the per-device full-T score matrix fits
(flash attention inside keeps it O(T) anyway).

Pattern per the public DeepSpeed-Ulysses formulation, expressed as XLA
collectives under one shard_map.  Differentiable end to end (all_to_all
transposes to the reverse all_to_all).
"""

import functools

import jax
from jax.sharding import PartitionSpec as P


def _attention(q, k, v, causal, scale, window=0):
    """Full-sequence attention on local heads [B, h, T, D] — the shared
    fused-attention lowering, so the flash kernel wherever that chooses it
    (window: sliding-window masking, since every head sees the FULL
    sequence here the op's banded mask applies globally)."""
    from ..ops import nn_ops  # noqa: F401  (registers fused_attention)
    from ..core.registry import get_op

    class _Ctx:
        rng_key = None

        def rng(self, attrs):  # pragma: no cover - attention needs no rng
            raise RuntimeError("no rng in fused attention")

    out = get_op("fused_attention").lower(
        _Ctx(), {"Q": [q], "K": [k], "V": [v]},
        {"causal": causal, "scale": scale, "window": int(window)},
    )
    return out["Out"][0]


def ulysses_attention(q, k, v, axis_name, causal=False, scale=None,
                      window=0):
    """Per-device body (call under shard_map): q/k/v [B, H, T_local, D]
    sharded on time -> output [B, H, T_local, D] sharded on time.

    all_to_all #1: scatter heads / gather time -> [B, H/n, T, D]
    local attention over full T on H/n heads
    all_to_all #2: scatter time / gather heads -> back.
    """
    n = jax.lax.psum(1, axis_name)
    B, H, Tl, D = q.shape
    assert H % n == 0, (
        "ulysses needs heads %d divisible by %s=%d" % (H, axis_name, n)
    )
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    def scatter_heads(x):  # [B, H, Tl, D] -> [B, H/n, n*Tl, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    def scatter_time(x):  # [B, H/n, n*Tl, D] -> [B, H, Tl, D]
        return jax.lax.all_to_all(
            x, axis_name, split_axis=2, concat_axis=1, tiled=True
        )

    qh, kh, vh = scatter_heads(q), scatter_heads(k), scatter_heads(v)
    out = _attention(qh, kh, vh, causal, scale, window)
    return scatter_time(out)


def ulysses_attention_sharded(q, k, v, mesh, axis_name="sp", causal=False,
                              window=0):
    """Convenience wrapper mirroring ring_attention_sharded: q/k/v
    [B, H, T, D] global, sharded over `axis_name` on the time dim."""
    from .mesh import shard_map

    spec = P(None, None, axis_name, None)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
    )
    def inner(ql, kl, vl):
        return ulysses_attention(ql, kl, vl, axis_name, causal=causal,
                                 window=window)

    return inner(q, k, v)
