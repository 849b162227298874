"""Does the compiler keep the rounding of a bfloat16 matmul result that the
AMP pass casts back to float32 at once (`mul` writing `<out>@RAW_BF16`, then
the cast-back: Nemotron's in-projection before PR 58)?  Prints, for the
device JAX finds, the share of the cast-back's float32 values that are NOT
bfloat16 values: 0.0 where the rounding is kept, ~1.0 where the compiler
folded the cast into the matmul and handed on the float32 accumulator
(`xla_allow_excess_precision`, on by default; 0.99995 on a v5e and on the
CPU, 0.0 under `XLA_FLAGS=--xla_allow_excess_precision=false`: my runs,
PR 58, PERF.md section 6).

    python tools/amp_castback_probe.py
"""
import jax
import jax.numpy as jnp
import numpy as np


def castback(x, w):
    return (x @ w).astype(jnp.float32)


def castback_sliced(x, w):
    z, xbc, dt = jnp.split(castback(x, w), [4096, 10240], axis=-1)
    return jax.nn.silu(z), xbc * 1.0, dt


def not_bf16(a):
    a = np.asarray(a)
    rounded = np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(
        jnp.float32))
    return float((a != rounded).mean())


def main():
    key = jax.random.key(0)
    x = jax.random.normal(key, (6144, 2688), jnp.float32).astype(jnp.bfloat16)
    w = (0.02 * jax.random.normal(jax.random.fold_in(key, 1), (2688, 10304),
                                  jnp.float32)).astype(jnp.bfloat16)
    print("device", jax.devices()[0].device_kind)
    print("cast-back alone: share of values that are not bfloat16 values",
          not_bf16(jax.jit(castback)(x, w)))
    _, xbc, dt = jax.jit(castback_sliced)(x, w)
    for name, a in (("xBC", xbc), ("dt", dt)):
        print("under a split, %s: share not bfloat16 values" % name,
              not_bf16(a))


if __name__ == "__main__":
    main()
