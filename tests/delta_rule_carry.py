"""What tests/test_kda_op.py (both decays) and tests/test_gdn_op.py hold
the carry's kernels (ops/kda_kernels.carry / carry_bwd, interpreted on the
CPU) against:
the equations of ops/kda_ops.py's docstring as ONE plain `lax.scan` over the
chunks, differentiated by jax, and the parts an inside would hand over,
drawn at random.  No kernel, no transposed state, no merged product here.

    U = U0 - W S;   O = (Q exp(G)) S + A_qk U
    S' = gamma S + (K exp(G_C - G))^T U          S_0 = 0, float32
"""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import kda_ops

PARTS = ("W", "U0", "A_qk", "Qexp(G)", "Kexp(G_C-G)", "gamma")
_F32 = jnp.float32


def random_parts(n, b, h, decay, dtype=_F32, dk=16, dv=8, seed=0,
                 gamma=(0.3, 1.0)):
    """An inside's six results for n chunks, chunks leading: the products'
    operands in `dtype`, U0 and the chunk's whole decay float32; the decay
    [n, b, h, dk] ("channel") or [n, b, h, 1] ("head"), drawn in `gamma`."""
    rng = np.random.default_rng(seed)
    c = kda_ops.CHUNK

    def draw(*shape, size):
        return jnp.asarray(rng.standard_normal((n, b, h) + shape) * size,
                           _F32)

    return (draw(c, dk, size=dk ** -0.5).astype(dtype),
            draw(c, dv, size=1.0),
            jnp.tril(draw(c, c, size=c ** -0.5)).astype(dtype),
            draw(c, dk, size=dk ** -0.5).astype(dtype),
            draw(c, dk, size=dk ** -0.5).astype(dtype),
            jnp.asarray(rng.uniform(*gamma, (n, b, h, dk if decay == "channel"
                                             else 1)), _F32))


def mix(parts, t=None, seed=3):
    """Weights for the result's elements (a gradient for it), [B, H, t,
    dv] float32; t: the parts' whole length where not given."""
    w, u0 = parts[:2]
    shape = w.shape[1:3] + (t or w.shape[0] * kda_ops.CHUNK, u0.shape[-1])
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       _F32)


def scan_carry(parts, dtype=_F32, states=False):
    """`parts` -> O [B, H, N C, dv] float32 by the docstring's equations,
    one chunk a step: every product's operands in `dtype`, accumulated in
    float32, the state float32.  `states`: the state every chunk entered
    with, [N, B, H, dk, dv], instead."""

    def mm(eq, x, y):
        return jnp.einsum(eq, x.astype(dtype), y.astype(dtype),
                          preferred_element_type=_F32, precision="highest")

    def step(s, xs):
        w, u0, a_qk, qg, kd, gamma = xs
        u = u0 - mm("bhtc,bhcv->bhtv", w, s)
        o = mm("bhtc,bhcv->bhtv", qg, s) + mm("bhti,bhiv->bhtv", a_qk, u)
        return gamma[..., None] * s + mm("bhtc,bhtv->bhcv", kd, u), (o, s)

    w, u0 = parts[:2]
    s0 = jnp.zeros(w.shape[1:3] + (w.shape[-1], u0.shape[-1]), _F32)
    _, (o, entered) = jax.lax.scan(step, s0, tuple(parts))
    if states:
        return entered
    o = jnp.moveaxis(o, 0, 2)
    return o.reshape(o.shape[:2] + (-1, o.shape[-1]))


def scan_grads(parts, mix):
    """The six parts' gradients of sum(O . mix), by jax.grad of the scan;
    the decay's as the decay is laid out."""
    return jax.grad(lambda *p: (scan_carry(p) * mix).sum(),
                    argnums=range(6))(*parts)


def kernel_grads(parts, mix, t=None):
    """The same through `kda_ops._carry_backward` (two kernels); a decay of
    one number a head sums its [.., dk] as `_gdn_bwd` does."""
    w, u0 = parts[:2]
    v = jax.ShapeDtypeStruct(w.shape[1:3] + (t or mix.shape[2],
                                             u0.shape[-1]), mix.dtype)
    got = kda_ops._carry_backward(parts, v, mix)
    if parts[5].shape[-1] == 1:
        got = got[:5] + (got[5].sum(-1, keepdims=True),)
    return got


def carry_calls(jaxpr):
    """The carry's pallas_call equations of a jaxpr, in order, those inside
    a `custom_vjp_call` included: the calls that carry a scratch."""
    found = []
    for eqn in getattr(jaxpr, "jaxpr", jaxpr).eqns:
        if eqn.primitive.name == "pallas_call":
            if eqn.params["grid_mapping"].num_scratch_operands:
                found.append(eqn)
            continue
        for inner in eqn.params.values():
            if hasattr(inner, "eqns") or hasattr(inner, "jaxpr"):
                found += carry_calls(inner)
    return found


def scratch_avals(eqn):
    n = eqn.params["grid_mapping"].num_scratch_operands
    return [v.aval for v in eqn.params["jaxpr"].invars[-n:]]
