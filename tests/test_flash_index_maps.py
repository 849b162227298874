"""The index maps of the causal flash kernels on the full grid (PR 56,
ops/pallas_kernels._band_inner): a step the causal mask skips names the
block the head's next live step reads, so the pipeline copies in the blocks
a head computes on and no other.  Bit-equality with the step-index maps
they replace, a host walk of every kernel's grid at the cells' shapes, and
the calls the maps must not reach.  (Beside tests/test_pallas_kernels.py,
whose file one worker of the driver's run holds for minutes already.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_pallas_kernels import BANDS, _pallas_eqns


def _step_index_maps(band, block_q, block_k, window, n_inner,
                     transposed=False, causal=False, traced=True):
    """pallas_kernels._band_inner as it was before PR 56: on the full grid
    every step names its own block."""
    from paddle_tpu.ops import pallas_kernels as pk

    if not band:
        return lambda o, step: step
    return lambda o, step: pk._band_step(o, step, block_q, block_k, window,
                                         n_inner, transposed, traced)[0]


@pytest.mark.parametrize("backward", ["one_kernel", "two_kernels"])
@pytest.mark.parametrize("bq,bk,blocks,d,dv", [
    (128, 128, 1, 64, 64), (256, 256, 1, 64, 64), (256, 256, 3, 64, 64),
    (512, 512, 2, 64, 64), (128, 128, 1, 192, 128), (256, 256, 1, 192, 128),
    (256, 256, 3, 192, 128), (128, 256, 3, 64, 64), (256, 128, 3, 192, 128)])
@pytest.mark.parametrize("band", sorted(BANDS))
def test_a_skipped_step_names_the_next_live_block_bit_for_bit(
        monkeypatch, band, bq, bk, blocks, d, dv, backward):
    """The index maps of PR 56 (a step the mask skips names the block the
    head's next live step reads) against the step-index maps they replace
    (_band_inner held at its old form): the live steps run in the same
    order on the same blocks, so o, lse, dq, dk, dv and the key bias's
    gradient are the same BITS, under a key bias and segments, in the one-
    and in the two-kernel backward, on square blocks and on blocks that are
    not (where a window on the full grid skips steps at both ends of a
    row)."""
    from paddle_tpu.ops import pallas_kernels as pk

    t = max(bq, bk) * blocks
    window = BANDS[band](t, max(bq, bk))
    rng = np.random.RandomState(bq + blocks + d)
    q, k, do = (jnp.asarray(rng.randn(1, t, n).astype("float32"))
                for n in (d, d, dv))
    v = jnp.asarray(rng.randn(1, t, dv).astype("float32"))
    kb = np.zeros((1, t), "float32")
    kb[:, 5:9] = -1e9
    kb[:, t - 70:t - 30] = -3.0
    kbias = jnp.asarray(kb)
    seg = jnp.asarray((np.arange(t) >= t // 3 + 5).astype("int32")[None, :])
    scale = d ** -0.5

    def both_passes():
        o, lse = pk._flash_fwd(q, k, v, kbias, True, scale, bq, bk, window,
                               seg=seg, interpret=True, by_class=True)
        if backward == "one_kernel":
            grads = pk._flash_bwd_fused(q, k, v, kbias, seg, o, lse, do,
                                        True, scale, bq, bk, window, True)
        else:
            grads = pk._flash_bwd(q, k, v, kbias, o, lse, do, True, scale,
                                  bq, bk, window=window, seg=seg,
                                  interpret=True)
        return (o, lse) + tuple(grads)

    new = both_passes()
    monkeypatch.setattr(pk, "_band_inner", _step_index_maps)
    old = both_passes()
    assert len(new) == len(old) == 6
    for a, b in zip(new, old):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# (T, window) of the flash cells' cores in 1024-blocks -> tiles a head
CELL_WALKS = {(4096, 0): 10, (6144, 0): 21, (8192, 0): 36, (8192, 2048): 21}


@pytest.mark.parametrize("transposed", [False, True],
                         ids=["k_innermost", "q_innermost"])
@pytest.mark.parametrize("t,window", sorted(CELL_WALKS))
def test_a_heads_walk_copies_in_only_blocks_it_computes_on(t, window,
                                                           transposed):
    """A host walk of a kernel's grid at the cells' shapes, forward (and the
    dq kernel: k innermost) and backward (q innermost): a step _band runs
    names its own block, a step it skips the block of the head's next live
    step (whatever it likes after the last; on a window's band grid, PR
    41's, the row's last live block again), so every block copied in is
    computed on: as many copies as tiles, less the one tile at a row's
    start that finds its block held from the row before; the traced maps
    say what the host's say, and tile_class_stats tells the benchmark the
    same.  The step-index maps copied in a block a grid step."""
    from paddle_tpu.ops import pallas_kernels as pk

    blk = 1024
    n = t // blk
    band = pk._band_grid(t, t, blk, blk, True, window, transposed)
    assert band == (3 if window else 0)
    walk = [(o, step) for o in range(n) for step in range(band or n)]
    host = pk._band_inner(band, blk, blk, window, n, transposed,
                          causal=True, traced=False)
    traced = pk._band_inner(band, blk, blk, window, n, transposed,
                            causal=True)
    named = [int(host(np.int64(o), np.int64(step))) for o, step in walk]
    assert named == [int(traced(jnp.int32(o), jnp.int32(step)))
                     for o, step in walk]

    def runs(o, step):
        """(does _band run the step, the inner block it computes on)"""
        inner, live = (pk._band_step(o, step, blk, blk, window, n,
                                     transposed, traced=False)
                       if band else (step, True))
        qi, ki = (inner, o) if transposed else (o, inner)
        return bool(pk._band(qi, ki, 0, blk, blk, True, window)[0]
                    and live), inner

    live = [runs(o, step) for o, step in walk]
    assert sum(r for r, _ in live) == CELL_WALKS[t, window]
    for at, (run, inner) in enumerate(live):
        if band:  # PR 41's walk: a row shorter than the band repeats its
            nxt = inner  # last live block
        else:  # a live step is its own next live step
            nxt = next((b for r, b in live[at:] if r), None)
        if nxt is not None:
            assert named[at] == nxt, (walk[at], run)
    fetches = 1 + sum(a != b for a, b in zip(named, named[1:]))
    assert fetches == CELL_WALKS[t, window] - 1
    assert pk._walk_fetches(t, blk, blk, window, transposed) == fetches
    said = pk.tile_class_stats(t, 128, blk, blk, window)
    assert said["fwd_fetches" if not transposed else "bwd_fetches"] \
        == fetches <= sum(said["tiles"].values())
    if not window:  # what the step-index maps copied in: every grid step
        old = _step_index_maps(0, blk, blk, window, n, transposed)
        named = [int(old(o, step)) for o, step in walk]
        assert 1 + sum(a != b for a, b in zip(named, named[1:])) == n * n


@pytest.mark.parametrize("call", ["traced_offset", "windowed_traced_offset",
                                  "not_causal"])
def test_a_traced_offset_keeps_index_maps_without_arithmetic(call):
    """flash_attention_piece under a traced q offset (the ring's and the
    serving path's chunks: which steps run is not known when the kernel is
    traced) and a call without a causal mask walk the full grid with index
    maps that hand the grid's indices on, in the forward and in both
    backward kernels, as before PR 56."""
    from paddle_tpu.ops import pallas_kernels as pk

    t, d, blk = 1024, 64, 256
    x = jax.ShapeDtypeStruct((2, t, d), jnp.float32)
    qoff = jax.ShapeDtypeStruct((1,), jnp.int32)

    def fn(q, k, v, qoff):
        o, lse = pk.flash_attention_piece(
            q, k, v, call != "not_causal", None, blk, blk,
            512 if call == "windowed_traced_offset" else 0,
            None if call == "not_causal" else qoff)
        return jnp.sum(o) + jnp.sum(lse)

    kernels = _pallas_eqns(jax.make_jaxpr(jax.grad(
        fn, argnums=(0, 1, 2)))(x, x, x, qoff).jaxpr)
    assert len(kernels) == 3
    for eqn in kernels:
        mapping = eqn.params["grid_mapping"]
        assert mapping.grid == (2, t // blk, t // blk)
        assert [len(bm.index_map_jaxpr.jaxpr.eqns)
                for bm in mapping.block_mappings] \
            == [0] * len(mapping.block_mappings)
