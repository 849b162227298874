"""Long-context attention, three ways: sliding-window flash attention on
one device, ring attention (K/V rotation) and Ulysses (all-to-all) over
a sequence-parallel mesh axis.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python examples/long_context_attention.py
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers, parallel


def single_chip_sliding_window():
    """Mistral-style local attention: each token sees the last 64
    positions.  Placed on a TPU at a length the kernel takes
    (nn_ops._flash_engages), the flash kernels walk the window's band
    only, so compute scales with the window, not the sequence length;
    here, on any device, the dense lowering builds the same band."""
    x = layers.data("x", shape=[4, 256, 32])  # [heads, T, d]
    att = layers.fused_attention(x, x, x, causal=True, window=64)
    out = layers.reduce_mean(att)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    xv = np.random.RandomState(0).rand(2, 4, 256, 32).astype("float32")
    (val,) = exe.run(feed={"x": xv}, fetch_list=[out])
    print("sliding-window attention mean:", float(np.ravel(val)[0]))


def sequence_parallel_ring_and_ulysses():
    """The same global attention computed two ways over an `sp` axis:
    ring (T/n memory, n ppermute hops) and Ulysses (two all_to_alls,
    heads shard instead of time)."""
    import jax

    n = len(jax.devices())
    mesh = parallel.make_mesh({"sp": n})
    B, H, T, D = 2, n, 16 * n, 16
    rng = np.random.RandomState(1)
    q = np.asarray(rng.rand(B, H, T, D), "float32")
    ring = parallel.ring.ring_attention_sharded(q, q, q, mesh, "sp",
                                                causal=True)
    uly = parallel.ulysses.ulysses_attention_sharded(q, q, q, mesh, "sp",
                                                     causal=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(uly),
                               rtol=2e-4, atol=2e-5)
    print("ring == ulysses over sp=%d, T=%d" % (n, T))

    # global sliding window ACROSS the ring: out-of-window chunks skip
    win = parallel.ring.ring_attention_sharded(
        q, q, q, mesh, "sp", causal=True, window=16)
    print("windowed ring over sp=%d: out %s finite=%s"
          % (n, win.shape, bool(np.isfinite(np.asarray(win)).all())))


if __name__ == "__main__":
    single_chip_sliding_window()
    sequence_parallel_ring_and_ulysses()
