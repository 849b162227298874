"""Pallas TPU kernels: blockwise (flash) attention.

Role parity with the reference's specialized kernel libraries — the cuDNN
fused-attention kernels (SURVEY §2.6) — but written for the TPU memory
hierarchy: attention blocked over q and k with an online softmax, so the
[T, T] score matrix never exists in HBM, forward or backward (custom_vjp).

Kernels run compiled on TPU and in interpreter mode elsewhere, so the same
code path is unit-testable on the CPU mesh.  `fused_attention`'s training
path chooses `flash_attention` from platform and shape
(nn_ops._flash_engages), and under that kernel's lengths its one-tile form
`short_attention` (nn_ops._short_engages); `flash_attention_piece` is
parallel/ring.py's.
"""

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

# the public primitive-kernel surface (tools/print_signatures tracks it
# in API.spec)
__all__ = [
    "flash_attention",
    "flash_attention_piece",
    "short_attention",
]

NEG_INF = -1e30


def _interpret():
    return jax.default_backend() != "tpu"


# Mosaic's default scoped-VMEM limit on a v5e is 16 MiB, and Pallas
# double-buffers every blocked operand: first contact with the chip
# refused a tile set at 16.10 MiB ("Scoped allocation with size 16.10M and
# limit 16.00M exceeded scoped vmem limit").  Every kernel therefore asks
# for the limit below (the v5e has 128 MiB of VMEM).
_VMEM_LIMIT_BYTES = 32 * 2 ** 20


def _mosaic_params():
    from jax.experimental.pallas import tpu as pltpu

    return pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT_BYTES)


def _sds(shape, dtype, *xs):
    """ShapeDtypeStruct whose vma (varying-mesh-axes) is the union of the
    inputs' — so pallas_call out_shapes type-check inside shard_map."""
    from ..parallel.mesh import vma_of

    return jax.ShapeDtypeStruct(shape, dtype, vma=vma_of(*xs))


def _note(family, n=1):
    """Trace-time pallas dispatch counter (bench attribution)."""
    from .kernel_tuning import note_kernel

    note_kernel(family, n)


# ---------------------------------------------------------------------------
# flash attention
#
# Blocked over BOTH q and k: grid (BH, nq, nk) with the k index innermost
# (sequential on a TPU core), carrying the online-softmax state (acc, m, l)
# in VMEM scratch across k steps.  Only [block, d] tiles of K/V are ever
# resident, so sequence length is bounded by HBM, not VMEM.  The forward
# saves the per-row logsumexp; the backward rebuilds [block_q, block_k]
# probability tiles from the saved lse — one fused kernel on the training
# path (flash_attention), two (dq and dk/dv/dkbias) where that one does not
# apply and on the ring path — so the [T, T] score matrix never exists in
# HBM in either pass.
# q, k, v, do reach the MXU in their own dtype; tiles and state are f32.
# Role parity: the cuDNN fused-attention kernels of SURVEY §2.6.
# ---------------------------------------------------------------------------
def _dot_nt(a, b):
    """a [m, d] x b [n, d] -> a b^T [m, n] in f32: the contraction over
    both minor dims that the MXU takes without a transpose."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _unpack_flash_refs(refs, has_qoff, has_seg):
    """Shared operand unpack for the three flash kernels (fwd/dq/dkv):
    the optional leading q base — a whole-array [1] SMEM operand, the
    scalar offset — then q/k/v/kbias and the optional segment-id pair.
    Returns (qo, q, k, v, kbias, seg_q, seg_k, remaining_refs); ONE
    copy so a new offset encoding cannot silently miss a backward
    kernel's causal base."""
    refs = list(refs)
    qo = refs.pop(0)[0] if has_qoff else 0
    q_ref, k_ref, v_ref, kb_ref = refs[:4]
    del refs[:4]
    sq_ref, sk_ref = (refs[:2] if has_seg else (None, None))
    if has_seg:
        del refs[:2]
    return qo, q_ref, k_ref, v_ref, kb_ref, sq_ref, sk_ref, refs


def _flash_fwd_kernel(*refs, block_q, block_k, nk,
                      causal, scale, window=0, has_qoff=False,
                      has_seg=False, band=0, tiles=None):
    from jax.experimental import pallas as pl

    qo, q_ref, k_ref, v_ref, kb_ref, sq_ref, sk_ref, refs = \
        _unpack_flash_refs(refs, has_qoff, has_seg)
    o_ref, lse_ref, acc_ref, m_ref, l_ref = refs
    qi = pl.program_id(1)
    ki = step = pl.program_id(2)
    if band:  # the innermost axis walks q block qi's band, `band` steps
        ki, live = _band_step(qi, step, block_q, block_k, window, nk)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    run, keep_fn = _band(qi, ki, qo, block_q, block_k, causal, window)
    if band:
        run = run & live

    def _compute(keep, rows=_ALL, cols=_ALL):
        """Rows `rows` of the q block against rows `cols` of the k block
        (slices of the tile; the whole tile by default), the scores masked
        by `keep` where one is given."""
        # operands reach the MXU in their own dtype (bf16 under AMP),
        # accumulation and the softmax state are f32
        q, k, v = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols]
        s = _dot_nt(q, k) * scale  # [bq, bk]
        s = s + kb_ref[0, :, cols].astype(jnp.float32)  # [1, bk] broadcast
        if has_seg:  # packing: keep within-segment scores only
            s = jnp.where(
                sq_ref[0, :, rows].reshape(-1, 1) == sk_ref[0, :, cols],
                s, NEG_INF)
        if keep is not None:
            s = keep(s)
        m_prev = m_ref[rows]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[rows] = acc_ref[rows] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[rows] = m_new

    if tiles is None:  # a tile's place is not known here: every one masked
        pl.when(run)(lambda: _compute(keep_fn))
    else:
        # a cut tile in strips of q rows, each against the k rows it can
        # see: a row's softmax state is rescaled once, as on a whole tile
        _tile_bodies(tiles, run, qi, ki, block_q, block_k, window, keep_fn,
                     _compute, strips_of="q")

    @pl.when(step == (band or nk) - 1)
    def _write():
        l = l_ref[:]
        safe_l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[:] / safe_l).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[:] + jnp.log(safe_l)).reshape(1, -1)


def _band(qi, ki, qo, block_q, block_k, causal, window, transposed=False):
    """Shared causal/window band logic for the flash kernels: returns
    (run, keep_fn) — the block-skip predicate and a function masking an
    [bq, bk] score tile ([bk, bq] when `transposed`) in GLOBAL positions
    (q base = qo)."""
    run = (ki * block_k < (qi + 1) * block_q + qo) if causal else (ki >= 0)
    if window:
        run = run & (ki * block_k + block_k - 1
                     >= qi * block_q + qo - window + 1)

    def keep_fn(s):
        if not causal:
            return s
        q_pos = qo + qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, int(transposed))
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - int(transposed))
        keep = q_pos >= k_pos
        if window:
            keep = keep & (q_pos - k_pos < window)
        return jnp.where(keep, s, NEG_INF)

    return run, keep_fn


# A tile by where it lies.  With a causal mask, blocks known when the
# kernel is traced and no traced q offset, a tile that _band lets run is one
# of four classes: wholly visible (under the diagonal and inside the band:
# computed without a mask, no iotas, compare or select), cut by the diagonal
# alone, cut by the band's lower edge alone, or cut by both (a window
# narrower than a block).  A tile cut by one edge is computed over its
# visible part, in `parts` strips of block / parts rows, each against the
# rows of the other side it can see and masked on the one square the cut
# crosses: (parts + 1) / (2 parts) of the tile's pairs, the others' exact
# zeros left out.  Strips need square blocks (the diagonal then cuts tile
# qi == ki corner to corner) and, on the band's edge, a window that is a
# multiple of the block (the edge then cuts tile qi - ki == window / block
# corner to corner); a tile cut otherwise, or by both, is the whole masked
# tile it was.  A kernel body holds one copy of the tile's computation a
# class that occurs and `parts` a class in strips: 1 + 4 a cut edge at most,
# and what a model traces once a kernel is that body (a copy costs ~0.05 s
# of a first step on a chip's host, PERF.md section 6, PR 53).
_ALL = slice(None)
_MAX_PARTS = 4


class _Tiles(NamedTuple):
    """How a kernel body computes each class of tile: `whole`, `both`: the
    class occurs; `diag`, `edge`: 0 the class does not occur, 1 the whole
    masked tile, more: that many strips."""
    whole: bool
    diag: int
    edge: int
    both: bool

    @property
    def bodies(self):
        """Copies of the tile's computation in a kernel body."""
        masked = self.both or self.diag == 1 or self.edge == 1
        return (int(self.whole) + int(masked)
                + sum(p for p in (self.diag, self.edge) if p > 1))


def _tile_counts(t, block_q, block_k, window):
    """Tiles of a head by class, {"whole", "diag", "edge", "both"}: causal
    self-attention at length t, under `window` where it is not 0."""
    qi = np.arange(t // block_q)[:, None]
    ki = np.arange(t // block_k)[None, :]
    q_lo, k_lo = qi * block_q, ki * block_k
    q_hi, k_hi = q_lo + block_q - 1, k_lo + block_k - 1
    run = k_lo <= q_hi
    inside = np.ones_like(run)
    if window:
        run = run & (q_lo - k_hi < window)
        inside = q_hi - k_lo < window
    under = k_hi <= q_lo
    return {"whole": int(np.sum(run & under & inside)),
            "diag": int(np.sum(run & ~under & inside)),
            "edge": int(np.sum(run & under & ~inside)),
            "both": int(np.sum(run & ~under & ~inside))}


def _strip_parts(block):
    """Strips a cut tile of square `block` blocks is taken in: their width
    block / parts a multiple of 128 (lse and delta are sliced by lanes),
    _MAX_PARTS at most; 1, the whole masked tile, under 256."""
    return max(1, min(_MAX_PARTS, block // 128))


def _fwd_strip_parts(t, block):
    """Strips the FORWARD takes a cut tile in: 2 where the sequence holds
    several blocks, the whole masked tile where one block holds it.  A strip
    of the forward ends in a row maximum and sum that the next matmul waits
    for, so more strips expose more of that chain: on a v5e
    (tools/attention_sweep.py --tile-classes both --parts 1x4,2x4,4x2, PR
    53; forward alone against every tile masked, 1 / 2 / 4 strips) T 4096
    d 128 0.997 / 0.926 / 0.961, T 6144 192 over 128 0.986 / 0.919 / 0.933,
    T 8192 d 64 0.994 / 0.959 / 0.976, d 128 0.993 / 0.954 / 0.974, its 2048
    band 0.995 / 0.929 / 0.946, d 256 0.990 / 0.935 / 0.936; GPT-2's one
    1024-tile a head (d 64) 0.996 / 1.082 / 1.066: strips cost it 7 %.  The
    backward rebuilds its tiles from the saved lse, has no such chain, and
    takes _strip_parts' four everywhere (0.72–0.94 against 0.82–0.95 in
    two)."""
    return min(2, _strip_parts(block)) if t > block else 1


def _tile_plan(t, block_q, block_k, window, parts):
    """The _Tiles of a causal kernel at length t whose cut tiles are taken
    in `parts` strips where strips apply."""
    n = _tile_counts(t, block_q, block_k, window)
    square = block_q == block_k

    def how(count, strips):
        return 0 if not count else parts if strips and parts > 1 else 1

    return _Tiles(whole=n["whole"] > 0,
                  diag=how(n["diag"], square),
                  edge=how(n["edge"], square and window % block_q == 0),
                  both=n["both"] > 0)


def _plan_pairs(tiles, n, block_q, block_k):
    """Score pairs a head's kernel computes under plan `tiles` over tile
    counts `n`: a whole or masked tile all of its pairs, a tile in p strips
    (p + 1) / (2 p) of them."""
    def share(p):
        return (p + 1) / (2.0 * p) if p > 1 else 1.0

    return int(block_q * block_k * (
        n["whole"] + n["both"] + n["diag"] * share(tiles.diag)
        + n["edge"] * share(tiles.edge)))


def tile_class_stats(t, d, block_q, block_k, window):
    """What the training path's kernels compute of a causal head at length
    t, width d, for the lowering's attribution: the score pairs visible
    (causal: t (t + 1) / 2; under a window each query's last `window`), the
    pairs the forward's and the backward's bodies compute, the copies of
    the tile's computation each body holds, the tiles by class, and the
    inner blocks each pass copies in (_walk_fetches: the tiles _band lets
    run where every block copied in is computed on, fewer where a block held
    over a row's end serves two tiles).  The two-kernel backward (a
    sequence past the one kernel's dq scratch) computes every tile _band
    lets run twice, masked, and copies in for both of its walks."""
    block_q, block_k = min(block_q, t), min(block_k, t)
    n = _tile_counts(t, block_q, block_k, window)
    fwd = _tile_plan(t, block_q, block_k, window,
                     _fwd_strip_parts(t, block_q))
    fwd_fetches = _walk_fetches(t, block_q, block_k, window)
    bwd_fetches = _walk_fetches(t, block_q, block_k, window, True)
    if _fused_bwd_applies(t, t, d):
        bwd = _tile_plan(t, block_q, block_k, window, _strip_parts(block_q))
        bwd_pairs, bwd_bodies = _plan_pairs(bwd, n, block_q, block_k), \
            bwd.bodies
    else:  # the dq kernel walks as the forward does, the dk/dv kernel q
        bwd_pairs, bwd_bodies = 2 * block_q * block_k * sum(n.values()), 1
        bwd_fetches += fwd_fetches
    seen = np.minimum(np.arange(t) + 1, window or t)
    return {"visible": int(np.sum(seen)),
            "fwd_pairs": _plan_pairs(fwd, n, block_q, block_k),
            "bwd_pairs": bwd_pairs, "fwd_bodies": fwd.bodies,
            "bwd_bodies": bwd_bodies, "tiles": n,
            "fwd_fetches": fwd_fetches, "bwd_fetches": bwd_fetches}


def _strip_keep(at, width, edge, transposed):
    """Masks the [width, width] square at lane `at` of a strip's scores,
    where the cut crosses it: in the square's own coordinates the diagonal
    keeps k <= q, the band's edge q < k (q along axis `transposed`)."""
    def keep(s):
        sq = s[:, at:at + width]
        q = jax.lax.broadcasted_iota(jnp.int32, sq.shape, int(transposed))
        k = jax.lax.broadcasted_iota(jnp.int32, sq.shape,
                                     1 - int(transposed))
        sq = jnp.where((q < k) if edge else (k <= q), sq, NEG_INF)
        pieces = ([s[:, :at]] if at else []) + [sq] + (
            [s[:, at + width:]] if at + width < s.shape[1] else [])
        return jnp.concatenate(pieces, axis=1) if len(pieces) > 1 else sq

    return keep


def _tile_bodies(tiles, run, qi, ki, block_q, block_k, window, keep_fn,
                 compute, strips_of, transposed=False):
    """Emits, under pl.when on the tile's class, the bodies plan `tiles`
    asks for.  compute(keep, q rows, k rows) computes a part of the tile,
    its scores masked by `keep` where that is not None.  A strip is
    block / parts rows of side `strips_of` ("q" or "k") against the rows of
    the other side it can see; the scores' minor axis is that other side."""
    from jax.experimental import pallas as pl

    under = ki * block_k + block_k - 1 <= qi * block_q
    inside = window and (qi + 1) * block_q - 1 - ki * block_k < window

    def cls(diag_cuts, edge_cuts):
        c = ~under if diag_cuts else under
        return c & (~inside if edge_cuts else inside) if window else c

    masked = []  # the classes that keep the whole masked tile

    def strips(parts, edge):
        w = block_q // parts
        # the other side's visible rows start at the tile's start (and the
        # cut crosses their last square) or end at its end (their first)
        low = edge == (strips_of == "k")
        for i in range(parts):
            own = slice(i * w, (i + 1) * w)
            other = slice(0, (i + 1) * w) if low else slice(i * w, block_q)
            keep = _strip_keep(i * w if low else 0, w, edge, transposed)
            compute(keep, *((own, other) if strips_of == "q"
                            else (other, own)))

    if tiles.whole:
        pl.when(run & cls(False, False))(lambda: compute(None))
    for parts, edge in ((tiles.diag, False), (tiles.edge, True)):
        if parts > 1:
            pl.when(run & cls(not edge, edge))(
                functools.partial(strips, parts, edge))
        elif parts:
            masked.append(cls(not edge, edge))
    if tiles.both:
        masked.append(cls(True, True))
    if masked:
        pl.when(run & functools.reduce(lambda a, b: a | b, masked))(
            lambda: compute(keep_fn))


# The band as a grid.  A windowed kernel without a traced q offset knows
# its band when it is traced, so its innermost grid axis is the band's
# width in blocks and its index maps address the band's blocks alone: the
# pipeline fetches no block that _band would then skip.  Forward (and the
# dq kernel) walk q block i's k blocks; the backward kernels, q innermost,
# walk k block j's q blocks (`transposed`).  A walk shorter than the
# widest one (the sequence's start, or its end transposed) repeats its
# last block, which the pipeline does not fetch again and `live` keeps
# from computing twice.  The triangle (no window, or one that covers the
# sequence) keeps the full grid, and its index maps name at a step _band
# skips the block the head's next live step reads (_band_inner): a block
# named twice in a row is not copied again, so the triangle copies in the
# blocks it computes on and no other.
def _band_span(o, block_q, block_k, window, n_inner, transposed=False,
               traced=True):
    """(first, last) inner block that _band runs (qo = 0, causal; under
    `window` where it is not 0) for outer block `o`: k blocks of q block o,
    or with `transposed` q blocks of k block o.  `o` is a traced int32
    scalar, or with traced=False a numpy array or int."""
    if traced:
        mx, mn = jnp.maximum, jnp.minimum

        def div(a, b):  # a >= 0
            return jax.lax.div(a, jnp.int32(b))
    else:
        mx, mn, div = np.maximum, np.minimum, np.floor_divide
    if transposed:
        first = div(o * block_k, block_q)
        last = mn(div(o * block_k + (block_k + window - 2), block_q),
                  n_inner - 1) if window else n_inner - 1
    else:
        first = div(mx(o * block_q - (window - 1), 0),
                    block_k) if window else 0
        last = mn(div(o * block_q + (block_q - 1), block_k), n_inner - 1)
    return first, last


def _band_step(o, step, block_q, block_k, window, n_inner, transposed=False,
               traced=True):
    """Step `step` of outer block o's walk: (inner block, live)."""
    first, last = _band_span(o, block_q, block_k, window, n_inner,
                             transposed, traced)
    mn = jnp.minimum if traced else np.minimum
    return mn(first + step, last), first + step <= last


def _band_inner(band, block_q, block_k, window, n_inner, transposed=False,
                causal=False, traced=True):
    """An index map's inner block of grid step (outer block, step).  On the
    band grid (band > 0) the band's step.  On the full grid of a `causal`
    kernel (no traced offset) a step _band runs names its own block, and a
    step it skips the block the head's next live step reads: the first
    live block of this row before it, the first of the next row after the
    last (forward: block 0 is copied in under the diagonal tile and held to
    the next row's start; backward: the diagonal's q block under the row
    before).  The step itself where the call is not causal, carries a
    traced offset, or one block holds the inner axis."""
    if band:
        return lambda o, step: _band_step(o, step, block_q, block_k, window,
                                          n_inner, transposed, traced)[0]
    if not causal or n_inner == 1:
        return lambda o, step: step
    t = n_inner * (block_q if transposed else block_k)
    n_outer = t // (block_k if transposed else block_q)
    window = window if window < t else 0
    mx, mn, where = ((jnp.maximum, jnp.minimum, jnp.where) if traced
                     else (np.maximum, np.minimum, np.where))

    def span(o):
        return _band_span(o, block_q, block_k, window, n_inner, transposed,
                          traced)

    def inner(o, step):
        first, last = span(o)
        if not window:  # the triangle: a row starts at block 0, a column
            # (transposed) ends at the last block
            return mx(step, first) if transposed else where(
                step <= last, step, 0)
        return where(step <= last, mx(step, first),
                     span(mn(o + 1, n_outer - 1))[0])

    return inner


def _band_grid(tq, tk, block_q, block_k, causal, window, transposed=False):
    """Steps of the innermost grid axis where a flash kernel walks the band
    alone, 0 where it keeps the full grid: no window, a window that covers
    the sequence, or a band as wide as the grid."""
    if not (causal and 0 < window < tq == tk):
        return 0
    n_outer, n_inner = tk // block_k, tq // block_q
    if not transposed:
        n_outer, n_inner = n_inner, n_outer
    first, last = _band_span(np.arange(n_outer), block_q, block_k, window,
                             n_inner, transposed, traced=False)
    width = int(np.max(last - first)) + 1
    return width if width < n_inner else 0


def band_grid_steps(t, block_q, block_k, window):
    """(grid steps a head's forward walks, tiles _band lets run) of a
    causal windowed flash_attention at length t, for the lowering's
    attribution: the full grid's count where _band_grid keeps it."""
    block_q, block_k = min(block_q, t), min(block_k, t)
    nq, nk = t // block_q, t // block_k
    first, last = _band_span(np.arange(nq), block_q, block_k, window, nk,
                             traced=False)
    width = _band_grid(t, t, block_q, block_k, True, window)
    return nq * (width or nk), int(np.sum(last - first + 1))


def _walk_fetches(t, block_q, block_k, window, transposed=False):
    """Blocks a head's walk of a causal kernel's grid copies in on its
    inner side (k, v and the key rows; `transposed`: q, do and the query
    rows): the pipeline copies a block in where the index maps name another
    than at the step before, so the times the name changes, and the head's
    first.  The maps are the kernel's own (_band_inner), run on the
    host."""
    n_outer, n_inner = t // block_q, t // block_k
    if transposed:
        n_outer, n_inner = n_inner, n_outer
    band = _band_grid(t, t, block_q, block_k, True, window, transposed)
    inner = _band_inner(band, block_q, block_k, window, n_inner, transposed,
                        causal=True, traced=False)
    o, step = np.meshgrid(np.arange(n_outer), np.arange(band or n_inner),
                          indexing="ij")
    named = np.broadcast_to(inner(o, step), o.shape).reshape(-1)
    return 1 + int(np.sum(named[1:] != named[:-1]))


def _flash_blocks(Tq, Tk, block_q, block_k, causal):
    block_q = min(block_q, Tq)
    block_k = min(block_k, Tk)
    assert Tq % block_q == 0 and Tk % block_k == 0, (
        "flash attention requires seq lens (%d, %d) divisible by block "
        "sizes (%d, %d) — pad the sequence" % (Tq, Tk, block_q, block_k)
    )
    assert not (causal and Tq != Tk), "causal requires Tq == Tk"
    return block_q, block_k


def _flash_fwd(q, k, v, kbias, causal, scale, block_q, block_k, window=0,
               qoff=None, seg=None, interpret=None, by_class=False):
    """q: [BH, Tq, d], k: [BH, Tk, d], v: [BH, Tk, dv] (the result is
    [BH, Tq, dv]; dv is d everywhere but under latent attention, whose
    scores are 192 wide over 128-wide values), kbias: [BH, Tk] additive
    key bias.
    window > 0 (causal only): sliding-window attention — each query sees
    only the last `window` key positions; without a traced offset the
    grid's innermost axis is the band's width (_band_grid).  qoff: optional [1] int32 GLOBAL
    q-position base relative to k's (traced; SMEM scalar) — the ring
    passes its chunk offset so causal/window masks apply in global
    positions.  seg: optional [BH, T] int32 segment ids (sequence
    packing; requires Tq == Tk) — rides as two more [BH, 1, X] rank-1
    operands, compared per score tile.  by_class (the training path's
    entry; causal self-attention without an offset): a tile is computed by
    where it lies (_tile_plan).  Returns (o, lse)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, d = q.shape
    Tk, dv = k.shape[1], v.shape[2]
    block_q, block_k = _flash_blocks(T, Tk, block_q, block_k,
                                     causal and qoff is None)
    assert not (window and not causal), "window attention requires causal"
    assert seg is None or T == Tk, "segment ids require Tq == Tk"
    if interpret is None:
        interpret = _interpret()
    nq, nk = T // block_q, Tk // block_k
    band = _band_grid(T, Tk, block_q, block_k,
                      causal and qoff is None, int(window))
    kblock = _band_inner(band, block_q, block_k, int(window), nk,
                         causal=causal and qoff is None)
    tiles = None
    if by_class and causal and qoff is None:
        tiles = _tile_plan(T, block_q, block_k, int(window),
                           _fwd_strip_parts(T, block_q))
    kernel = functools.partial(
        _flash_fwd_kernel, block_q=block_q, block_k=block_k, nk=nk,
        causal=causal, scale=scale, window=int(window),
        has_qoff=qoff is not None,
        has_seg=seg is not None, band=band, tiles=tiles,
    )
    # 2D [BH, X] operands ride as [BH, 1, X] so every block keeps a
    # Mosaic-legal last-two-dims shape ((1, blk): second-minor equals the
    # array dim, minor is the 128-multiple block)
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, kblock(i, j), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, kblock(i, j), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, kblock(i, j)),
                     memory_space=pltpu.VMEM),
    ]
    args = [q, k, v, kbias.reshape(BH, 1, Tk)]
    if seg is not None:
        seg3 = seg.astype(jnp.int32).reshape(BH, 1, T)
        in_specs += [
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda b, i, j: (b, 0, kblock(i, j)),
                         memory_space=pltpu.VMEM),
        ]
        args += [seg3, seg3]
    if qoff is not None:
        in_specs.insert(0, pl.BlockSpec(memory_space=pltpu.SMEM))
        args.insert(0, qoff.astype(jnp.int32).reshape(1))
    o, lse = pl.pallas_call(
        kernel,
        grid=(BH, nq, band or nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            _sds((BH, T, dv), q.dtype, q, k, v),
            _sds((BH, 1, T), jnp.float32, q, k, v),
        ],  # lse is over q rows; k-side shapes use Tk
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*args)
    return o, lse.reshape(BH, T)


def _flash_dq_kernel(*refs, block_q, block_k, nk, causal, scale,
                     window=0, has_qoff=False, has_seg=False, band=0):
    from jax.experimental import pallas as pl

    qo, q_ref, k_ref, v_ref, kb_ref, sq_ref, sk_ref, refs = \
        _unpack_flash_refs(refs, has_qoff, has_seg)
    do_ref, lse_ref, delta_ref, dq_ref, dq_acc = refs
    qi = pl.program_id(1)
    ki = step = pl.program_id(2)
    if band:  # as the forward: `band` steps over q block qi's k blocks
        ki, live = _band_step(qi, step, block_q, block_k, window, nk)

    @pl.when(step == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    run, keep_fn = _band(qi, ki, qo, block_q, block_k, causal, window)
    if band:
        run = run & live

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0].reshape(-1, 1)  # [bq, 1]
        delta = delta_ref[0].reshape(-1, 1)
        s = _dot_nt(q, k) * scale
        s = s + kb_ref[0].astype(jnp.float32)
        if has_seg:
            s = jnp.where(
                sq_ref[0].reshape(-1, 1) == sk_ref[0], s, NEG_INF)
        s = keep_fn(s)
        # rows with NO visible key (possible under qoff+window) carry the
        # lse sentinel: their forward output is defined-garbage by
        # contract, so their grads are 0 — without this guard
        # exp(s - lse) would be 1 on every masked entry of such rows
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        ds = p * (_dot_nt(do, v) - delta)
        dq_acc[:] = dq_acc[:] + scale * jnp.dot(
            ds.astype(k.dtype), k, preferred_element_type=jnp.float32)

    @pl.when(step == (band or nk) - 1)
    def _write():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_dkv_kernel(*refs, block_q, block_k, nq, causal, scale,
                      window=0, has_qoff=False, has_seg=False, band=0):
    from jax.experimental import pallas as pl

    qo, q_ref, k_ref, v_ref, kb_ref, sq_ref, sk_ref, refs = \
        _unpack_flash_refs(refs, has_qoff, has_seg)
    (do_ref, lse_ref, delta_ref,
     dk_ref, dv_ref, dkb_ref, dk_acc, dv_acc, dkb_acc) = refs
    ki = pl.program_id(1)
    qi = step = pl.program_id(2)
    if band:  # `band` steps over k block ki's q blocks
        qi, live = _band_step(ki, step, block_q, block_k, window, nq,
                              transposed=True)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        dkb_acc[:] = jnp.zeros_like(dkb_acc)

    run, keep_fn = _band(qi, ki, qo, block_q, block_k, causal, window)
    if not causal:
        run = qi >= 0  # this grid iterates q innermost
    if band:
        run = run & live

    @pl.when(run)
    def _compute():
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse = lse_ref[0].reshape(-1, 1)
        delta = delta_ref[0].reshape(-1, 1)
        s = _dot_nt(q, k) * scale
        s = s + kb_ref[0].astype(jnp.float32)
        if has_seg:
            s = jnp.where(
                sq_ref[0].reshape(-1, 1) == sk_ref[0], s, NEG_INF)
        s = keep_fn(s)
        # undefined-row grad guard (see _flash_dq_kernel)
        p = jnp.where(lse <= NEG_INF / 2, 0.0, jnp.exp(s - lse))
        dv_acc[:] = dv_acc[:] + jnp.dot(
            p.T.astype(do.dtype), do, preferred_element_type=jnp.float32)
        ds = p * (_dot_nt(do, v) - delta)
        dk_acc[:] = dk_acc[:] + scale * jnp.dot(
            ds.T.astype(q.dtype), q, preferred_element_type=jnp.float32)
        dkb_acc[:] = dkb_acc[:] + jnp.sum(ds, axis=0, keepdims=True)

    @pl.when(step == (band or nq) - 1)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        dkb_ref[0] = dkb_acc[:]  # [1, block_k] both sides


def _flash_bwd(q, k, v, kbias, o, lse, do, causal, scale, block_q, block_k,
               dlse=None, window=0, qoff=None, seg=None, interpret=None):
    """Blocked backward: returns (dq, dk, dv, dkbias[BH,Tk] f32).

    dlse: optional cotangent of the lse output (the chunk-merge path of
    ring attention differentiates through lse).  d lse / d s_ij = p_ij, so
    it folds into the delta term: ds = p * (dp - (delta - dlse))."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, d = q.shape
    Tk, dv = k.shape[1], v.shape[2]
    block_q, block_k = _flash_blocks(T, Tk, block_q, block_k,
                                     causal and qoff is None)
    if interpret is None:
        interpret = _interpret()
    nq, nk = T // block_q, Tk // block_k
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    has_qoff = qoff is not None
    qoff_arg = [qoff.astype(jnp.int32).reshape(1)] if has_qoff else []
    # 2D [BH, X] operands ride as [BH, 1, X] (Mosaic-legal blocks; see
    # _flash_fwd)
    kb3 = kbias.reshape(BH, 1, Tk)
    lse3 = lse.reshape(BH, 1, T)
    delta3 = delta.reshape(BH, 1, T)
    seg3 = (seg.astype(jnp.int32).reshape(BH, 1, T)
            if seg is not None else None)
    # the band grids of the forward (dq pass) and of the fused backward
    # (dk/dv pass), where there is no traced offset
    static = causal and not has_qoff
    band_k = _band_grid(T, Tk, block_q, block_k, static, int(window))
    band_q = _band_grid(T, Tk, block_q, block_k, static, int(window),
                        transposed=True)
    kblock = _band_inner(band_k, block_q, block_k, int(window), nk,
                         causal=static)
    qblock = _band_inner(band_q, block_q, block_k, int(window), nq,
                         transposed=True, causal=static)

    q_spec_q = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    k_spec_q = pl.BlockSpec((1, block_k, d),
                            lambda b, i, j: (b, kblock(i, j), 0),
                            memory_space=pltpu.VMEM)
    # v and do are dv wide (d everywhere but under latent attention)
    v_spec_q = pl.BlockSpec((1, block_k, dv),
                            lambda b, i, j: (b, kblock(i, j), 0),
                            memory_space=pltpu.VMEM)
    do_spec_q = pl.BlockSpec((1, block_q, dv), lambda b, i, j: (b, i, 0),
                             memory_space=pltpu.VMEM)
    kb_spec_q = pl.BlockSpec((1, 1, block_k),
                             lambda b, i, j: (b, 0, kblock(i, j)),
                             memory_space=pltpu.VMEM)
    row_spec_q = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i),
                              memory_space=pltpu.VMEM)
    smem = [pl.BlockSpec(memory_space=pltpu.SMEM)] if has_qoff else []
    seg_specs_q = ([row_spec_q, kb_spec_q] if seg is not None else [])
    seg_args = ([seg3, seg3] if seg is not None else [])
    dq = pl.pallas_call(
        functools.partial(_flash_dq_kernel, block_q=block_q, block_k=block_k,
                          nk=nk, causal=causal, scale=scale,
                          window=int(window), has_qoff=has_qoff,
                          has_seg=seg is not None, band=band_k),
        grid=(BH, nq, band_k or nk),
        in_specs=smem + [q_spec_q, k_spec_q, v_spec_q, kb_spec_q]
        + seg_specs_q + [do_spec_q, row_spec_q, row_spec_q],
        out_specs=q_spec_q,
        out_shape=_sds((BH, T, d), q.dtype, q, k, v, do),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*(qoff_arg + [q, k, v, kb3] + seg_args + [do, lse3, delta3]))

    # dk/dv pass: grid iterates q blocks innermost for each k block
    q_spec_k = pl.BlockSpec((1, block_q, d),
                            lambda b, i, j: (b, qblock(i, j), 0),
                            memory_space=pltpu.VMEM)
    k_spec_k = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    v_spec_k = pl.BlockSpec((1, block_k, dv), lambda b, i, j: (b, i, 0),
                            memory_space=pltpu.VMEM)
    do_spec_k = pl.BlockSpec((1, block_q, dv),
                             lambda b, i, j: (b, qblock(i, j), 0),
                             memory_space=pltpu.VMEM)
    kb_spec_k = pl.BlockSpec((1, 1, block_k), lambda b, i, j: (b, 0, i),
                             memory_space=pltpu.VMEM)
    row_spec_k = pl.BlockSpec((1, 1, block_q),
                              lambda b, i, j: (b, 0, qblock(i, j)),
                              memory_space=pltpu.VMEM)
    seg_specs_k = ([row_spec_k, kb_spec_k] if seg is not None else [])
    dk, dv, dkb = pl.pallas_call(
        functools.partial(_flash_dkv_kernel, block_q=block_q, block_k=block_k,
                          nq=nq, causal=causal, scale=scale,
                          window=int(window), has_qoff=has_qoff,
                          has_seg=seg is not None, band=band_q),
        grid=(BH, nk, band_q or nq),
        in_specs=smem + [q_spec_k, k_spec_k, v_spec_k, kb_spec_k]
        + seg_specs_k + [do_spec_k, row_spec_k, row_spec_k],
        out_specs=[k_spec_k, v_spec_k, kb_spec_k],
        out_shape=[
            _sds((BH, Tk, d), k.dtype, q, k, v, do),
            _sds((BH, Tk, dv), v.dtype, q, k, v, do),
            _sds((BH, 1, Tk), jnp.float32, q, k, v, do),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
        ],
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*(qoff_arg + [q, k, v, kb3] + seg_args + [do, lse3, delta3]))
    return dq, dk, dv, dkb.reshape(BH, Tk)


def _dense_attention(q, k, v, causal, scale, kbias=None, window=0,
                     seg=None, qoff=None):
    """XLA reference implementation (used as the non-pallas fallback).
    seg: optional [BH, T] int segment ids (sequence packing) — query i
    may attend key j only when seg[i] == seg[j]; the compare fuses into
    the softmax, no mask tensor lives in HBM.  qoff: optional traced
    GLOBAL q-position base (chunked decode): query i sits at global
    position qoff + i, keys at their indices — Tq may differ from Tk."""
    s = jnp.einsum("bqd,bkd->bqk", q, k).astype(jnp.float32) * scale
    if kbias is not None:
        s = s + kbias[:, None, :].astype(jnp.float32)
    if seg is not None:
        s = jnp.where(seg[:, :, None] == seg[:, None, :], s, NEG_INF)
    if causal:
        Tq, Tk = q.shape[1], k.shape[1]
        if qoff is not None:
            q_pos = (jnp.asarray(qoff).reshape(()).astype(jnp.int32)
                     + jnp.arange(Tq, dtype=jnp.int32))
            k_pos = jnp.arange(Tk, dtype=jnp.int32)
            keep = q_pos[:, None] >= k_pos[None, :]
            if window:
                keep = keep & (q_pos[:, None] - k_pos[None, :] < int(window))
            s = jnp.where(keep[None], s, NEG_INF)
        else:
            mask = jnp.tril(jnp.ones((Tq, Tq), bool))
            if window:
                mask = mask & ~jnp.tril(jnp.ones((Tq, Tq), bool),
                                        -int(window))
            s = jnp.where(mask[None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p.astype(q.dtype), v)


# The training path's backward is ONE kernel.  Its grid walks (BH, nk, nq)
# with q innermost, so dk / dv accumulate in a [bk, d] scratch per k block
# as in the dk/dv kernel above, and dq — which sums over k blocks, the
# OUTER loop — accumulates in a whole-sequence [T, d] f32 scratch that
# stays resident while one (batch, head) row is walked and is written once.
# The tiles are TRANSPOSED, [bk, bq]: the per-query lse / delta rows then
# broadcast along sublanes as they are stored ((1, bq) blocks), dv and dk
# are plain matmuls, and only dq contracts over the tile's major dim.
# Against the two-kernel form it computes s, p and dp once instead of
# twice (five matmuls a tile, not seven) and is one Mosaic call.
_FUSED_BWD_DQ_BYTES = 4 * 2 ** 20  # [T, d] f32: T <= 8192 at d = 128
# latent attention's 192-wide q: the same T <= 8192 (tools/
# mla_kernel_sweep.py on a v5e, PR 37: one kernel 13.4 against two 17.5 ms
# at T = 6144, 23.2 against 29.7 at 8192; narrower heads keep the limit
# they were swept under)
_FUSED_BWD_DQ_BYTES_WIDE = 6 * 2 ** 20
# Qwen3-Next's 256-wide heads: T <= 8192 again (tools/mla_kernel_sweep.py
# --width 256 --heads 16 on a v5e, PR 48: one kernel 7.95 against two 10.44
# ms at T = 6144, 13.51 against 17.73 at 8192, forward + backward)
_FUSED_BWD_DQ_BYTES_256 = 8 * 2 ** 20


def _fused_bwd_dq_limit(d):
    """The largest [T, d] float32 dq scratch the one-kernel backward is
    given at head width d: each width keeps the limit it was swept
    under."""
    return (_FUSED_BWD_DQ_BYTES if d <= 128
            else _FUSED_BWD_DQ_BYTES_WIDE if d <= 192
            else _FUSED_BWD_DQ_BYTES_256)


def _fused_bwd_applies(tq, tk, d):
    """Self-attention whose [T, d] float32 dq fits the scratch."""
    return tq == tk and tq * d * 4 <= _fused_bwd_dq_limit(d)


def _flash_bwd_fused_kernel(*refs, block_q, block_k, nq, nk, causal, scale,
                            window, has_kb, has_seg, band=0, tiles=None):
    from jax.experimental import pallas as pl

    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    del refs[:3]
    kb_ref = refs.pop(0) if has_kb else None
    sq_ref, sk_ref = (refs.pop(0), refs.pop(0)) if has_seg else (None, None)
    do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref = refs[:6]
    del refs[:6]
    dkb_ref = refs.pop(0) if has_kb else None
    dq_acc, dk_acc, dv_acc = refs[:3]
    dkb_acc = refs[3] if has_kb else None
    ki = pl.program_id(1)
    qi = step = pl.program_id(2)
    if band:  # `band` steps over k block ki's q blocks
        qi, live = _band_step(ki, step, block_q, block_k, window, nq,
                              transposed=True)

    @pl.when((ki == 0) & (step == 0))
    def _init_row():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(step == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)
        if has_kb:
            dkb_acc[:] = jnp.zeros_like(dkb_acc)

    run, keep_fn = _band(qi, ki, 0, block_q, block_k, causal, window,
                         transposed=True)
    if band:
        run = run & live

    def _compute(keep, qs=_ALL, ks=_ALL):
        """Rows `ks` of the k block against rows `qs` of the q block
        (slices of the tile; the whole tile by default), the transposed
        scores masked by `keep` where one is given."""
        q, k, v, do = q_ref[0, qs], k_ref[0, ks], v_ref[0, ks], do_ref[0, qs]
        st = _dot_nt(k, q) * scale  # [bk, bq]
        if has_kb:
            st = st + kb_ref[0, :, ks].astype(jnp.float32).reshape(-1, 1)
        if has_seg:
            st = jnp.where(
                sk_ref[0, :, ks].reshape(-1, 1) == sq_ref[0, :, qs],
                st, NEG_INF)
        if keep is not None:
            st = keep(st)
        pt = jnp.exp(st - lse_ref[0, :, qs])  # lse, delta: [1, bq]
        dv_acc[ks] = dv_acc[ks] + jnp.dot(
            pt.astype(do.dtype), do, preferred_element_type=jnp.float32)
        dst = pt * (_dot_nt(v, do) - delta_ref[0, :, qs])
        if has_kb:
            dkb_acc[:, ks] = dkb_acc[:, ks] + jnp.sum(
                dst, axis=1, keepdims=True).reshape(1, -1)
        dsc = dst.astype(q.dtype)
        dk_acc[ks] = dk_acc[ks] + scale * jnp.dot(
            dsc, q, preferred_element_type=jnp.float32)
        q0 = qs.indices(block_q)[0]  # these q rows' place in dq's scratch
        first = qi * block_q + q0 if q0 else qi * block_q
        rows = pl.ds(pl.multiple_of(first, math.gcd(block_q, q0)),
                     q.shape[0])
        dq_acc[rows, :] = dq_acc[rows, :] + scale * jax.lax.dot_general(
            dsc, k, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # [bq, d]

    if tiles is None:  # non-causal: every tile whole, and none masked
        pl.when(run)(lambda: _compute(keep_fn))
    else:
        # a cut tile in strips of k rows, each against the q rows that see
        # it: dk and dv take one update a k row, as on a whole tile
        _tile_bodies(tiles, run, qi, ki, block_q, block_k, window, keep_fn,
                     _compute, strips_of="k", transposed=True)

    @pl.when(step == (band or nq) - 1)
    def _write():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)
        if has_kb:
            dkb_ref[0] = dkb_acc[:]

    @pl.when((ki == nk - 1) & (step == (band or nq) - 1))
    def _write_row():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_fused(q, k, v, kbias, seg, o, lse, do, causal, scale, block_q,
                     block_k, window, interpret):
    """(dq, dk, dv, dkbias [BH, T] f32 or None) from the saved o and lse,
    self-attention (Tq == Tk) without a q offset: one pallas_call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, T, d = q.shape
    dv = v.shape[2]  # v, do and dv's width: d but under latent attention
    block_q, block_k = _flash_blocks(T, T, block_q, block_k, causal)
    nq, nk = T // block_q, T // block_k
    band = _band_grid(T, T, block_q, block_k, causal, int(window),
                      transposed=True)
    qblock = _band_inner(band, block_q, block_k, int(window), nq,
                         transposed=True, causal=causal)
    tiles = (_tile_plan(T, block_q, block_k, int(window),
                        _strip_parts(block_q)) if causal else None)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def spec(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    q_spec = spec((1, block_q, d), lambda b, i, j: (b, qblock(i, j), 0))
    k_spec = spec((1, block_k, d), lambda b, i, j: (b, i, 0))
    do_spec = spec((1, block_q, dv), lambda b, i, j: (b, qblock(i, j), 0))
    v_spec = spec((1, block_k, dv), lambda b, i, j: (b, i, 0))
    qrow_spec = spec((1, 1, block_q), lambda b, i, j: (b, 0, qblock(i, j)))
    krow_spec = spec((1, 1, block_k), lambda b, i, j: (b, 0, i))
    in_specs, args = [q_spec, k_spec, v_spec], [q, k, v]
    if kbias is not None:
        in_specs.append(krow_spec)
        args.append(kbias.reshape(BH, 1, T))
    if seg is not None:
        seg3 = seg.astype(jnp.int32).reshape(BH, 1, T)
        in_specs += [qrow_spec, krow_spec]
        args += [seg3, seg3]
    in_specs += [do_spec, qrow_spec, qrow_spec]
    args += [do, lse.reshape(BH, 1, T), delta.reshape(BH, 1, T)]
    out_specs = [spec((1, T, d), lambda b, i, j: (b, 0, 0)), k_spec, v_spec]
    out_shape = [_sds((BH, T, d), q.dtype, q, k, v, do),
                 _sds((BH, T, d), k.dtype, q, k, v, do),
                 _sds((BH, T, dv), v.dtype, q, k, v, do)]
    scratch = [pltpu.VMEM((T, d), jnp.float32),
               pltpu.VMEM((block_k, d), jnp.float32),
               pltpu.VMEM((block_k, dv), jnp.float32)]
    if kbias is not None:
        out_specs.append(krow_spec)
        out_shape.append(_sds((BH, 1, T), jnp.float32, q, k, v, do))
        scratch.append(pltpu.VMEM((1, block_k), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, block_q=block_q, block_k=block_k,
            nq=nq, nk=nk, causal=causal, scale=scale, window=int(window),
            has_kb=kbias is not None, has_seg=seg is not None, band=band,
            tiles=tiles),
        grid=(BH, nk, band or nq),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*args)
    dkb = outs[3].reshape(BH, T) if kbias is not None else None
    return outs[0], outs[1], outs[2], dkb


# The two entries of the training path are jitted at module level: every
# layer of a model calls them with the same shapes and static
# configuration, so the forward op, the grad op's re-traced forward and
# the backward hit jax's trace cache after the first layer and lower to ONE
# shared function each — a step's StableHLO carries each Mosaic payload
# once, and the host traces each kernel body once, however deep the model.
_FLASH_STATICS = ("causal", "scale", "block_q", "block_k", "window",
                  "interpret")


@functools.partial(jax.jit, static_argnames=_FLASH_STATICS)
def _flash_fwd_call(q, k, v, kbias, seg, *, causal, scale, block_q, block_k,
                    window, interpret):
    kb = kbias if kbias is not None else jnp.zeros(k.shape[:2], jnp.float32)
    return _flash_fwd(q, k, v, kb, causal, scale, block_q, block_k, window,
                      seg=seg, interpret=interpret, by_class=True)


@functools.partial(jax.jit, static_argnames=_FLASH_STATICS)
def _flash_bwd_call(q, k, v, kbias, seg, o, lse, do, *, causal, scale,
                    block_q, block_k, window, interpret):
    T, d = q.shape[1:]
    if _fused_bwd_applies(T, k.shape[1], d):
        return _flash_bwd_fused(q, k, v, kbias, seg, o, lse, do, causal,
                                scale, block_q, block_k, window, interpret)
    kb = kbias if kbias is not None else jnp.zeros(k.shape[:2], jnp.float32)
    dq, dk, dv, dkb = _flash_bwd(
        q, k, v, kb, o, lse, do, causal, scale, block_q, block_k,
        window=window, seg=seg, interpret=interpret)
    return dq, dk, dv, (None if kbias is None else dkb)


def _flash_statics(q, causal, scale, block_q, block_k, window):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return dict(causal=bool(causal), scale=float(scale),
                block_q=int(block_q), block_k=int(block_k),
                window=int(window), interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def flash_attention(q, k, v, kbias=None, causal=False, scale=None,
                    block_q=128, block_k=128, window=0, seg=None):
    """Fused attention, q: [BH, Tq, d], k: [BH, Tk, d], v: [BH, Tk, dv]
    -> [BH, Tq, dv] (flash-style online softmax; dv may differ from d:
    latent attention scores 192 wide over 128-wide values, the score
    tile one 192-wide contraction): q, k, v reach the MXU in their own
    dtype, the scores,
    the running max / sum and the saved logsumexp are f32 and never leave
    VMEM.  kbias: optional [BH, Tk] additive key bias (the padding-mask
    row, indexed by key position).  window > 0 (causal): sliding-window
    local attention over the last `window` positions — blocks wholly above
    the diagonal or out of the window are skipped in every kernel, and a
    window inside the sequence makes the band the kernels' grid
    (_band_grid: no block outside it is fetched), so compute and grid
    steps scale with the band, not T^2.  seg: optional [BH, T] int
    segment ids (sequence packing, Tq == Tk): scores across segment
    boundaries are masked inside every kernel — rank-1 operands only, no
    [T, T] mask.  Forward and backward are owned here (custom_vjp): the
    backward rebuilds probability tiles from the saved o and lse in one
    kernel (two where Tq != Tk or the sequence outgrows its dq scratch)."""
    return _flash_vjp_fwd(q, k, v, kbias, causal, scale, block_q, block_k,
                          window, seg)[0]


def _flash_vjp_fwd(q, k, v, kbias, causal, scale, block_q, block_k,
                   window=0, seg=None):
    # the primal above makes this same call and drops lse, so a forward
    # op's kernel and its grad op's re-traced one are one instruction
    # after CSE
    o, lse = _flash_fwd_call(
        q, k, v, kbias, seg,
        **_flash_statics(q, causal, scale, block_q, block_k, window))
    return o, (q, k, v, kbias, seg, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, window, res, do):
    q, k, v, kbias, seg, o, lse = res
    dq, dk, dv, dkb = _flash_bwd_call(
        q, k, v, kbias, seg, o, lse, do,
        **_flash_statics(q, causal, scale, block_q, block_k, window))
    # integer segment ids get the mandatory float0 cotangent
    dseg = (None if seg is None
            else np.zeros(seg.shape, dtype=jax.dtypes.float0))
    dkb_out = None if kbias is None else dkb.astype(kbias.dtype)
    return dq, dk, dv, dkb_out, dseg


flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention_piece(q, k, v, causal=False, scale=None,
                          block_q=128, block_k=128, window=0, qoff=None):
    """Unmerged attention piece for ring/Ulysses sequence parallelism:
    returns (o, lse) where o is softmax-normalized within this K/V chunk
    and lse is the per-row logsumexp.  Two pieces merge exactly via
    lse = logaddexp(lse1, lse2); o = o1*exp(lse1-lse) + o2*exp(lse2-lse)
    (see parallel/ring.py).  Differentiable in q/k/v including through the
    lse output (its cotangent folds into the backward's delta term).
    window/qoff: sliding-window masking and a traced GLOBAL q-position
    offset (SMEM scalar), so ring callers mask diagonal AND off-diagonal
    chunks exactly in global positions."""
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    kb = jnp.zeros(k.shape[:2], jnp.float32)
    _note("attention")
    return _flash_fwd(q, k, v, kb, causal, scale, block_q, block_k, window,
                      qoff)


def _piece_vjp_fwd(q, k, v, causal, scale, block_q, block_k, window=0,
                   qoff=None):
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    kb = jnp.zeros(k.shape[:2], jnp.float32)
    _note("attention")
    o, lse = _flash_fwd(q, k, v, kb, causal, scale, block_q, block_k, window,
                        qoff)
    return (o, lse), (q, k, v, o, lse, qoff)


def _piece_vjp_bwd(causal, scale, block_q, block_k, window, res, cts):
    q, k, v, o, lse, qoff = res
    do, dlse = cts
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    kb = jnp.zeros(k.shape[:2], jnp.float32)
    dq, dk, dv, _ = _flash_bwd(
        q, k, v, kb, o, lse, do, causal, scale, block_q, block_k, dlse=dlse,
        window=window, qoff=qoff)
    return dq, dk, dv, None


flash_attention_piece.defvjp(_piece_vjp_fwd, _piece_vjp_bwd)


# ---------------------------------------------------------------------------
# short-sequence attention: the whole sequence one tile, several heads a
# grid step
#
# Under the blockwise kernel's reach (nn_ops._FLASH_MIN_T) a head's scores
# are ONE tile, and a grid that walks a head a step pays a step's fixed cost
# for ~0.1 us of MXU work (tools/attention_sweep.py, PR 29: 3.64 ms against
# dense's 2.85 at T = 256).  Here a grid step holds G heads: the scores of
# all G one batched product, a plain softmax over the whole row, no
# accumulation across steps, no scratch, no tile classes; the backward is
# one call that rebuilds the probabilities from q, k and the saved rows of
# each query's max and 1 / sum.
#
# What bounds these shapes on a v5e is not the arithmetic but the copies: a
# [BH, T, 64] bfloat16 array is stored in (16, 128) tiles, half of every
# tile padding, and a kernel that only copied such blocks in and out took
# 0.83 ms forward and 1.62 ms backward at BH 1024 x T 256 (PR 62,
# CHANGES.md): dense's whole 2.87.  So the kernels take every operand
# TRANSPOSED, [BH, d, T]: the sequence fills the lanes, the 64 head
# channels are whole sublane tiles, no byte of a copy is padding, and XLA
# reaches that layout from a projection's [B, T, H d] in one transposing
# copy where [B, H, T, d] takes it two.  A sequence shorter than the 128
# lanes shares them with its neighbours (_short_pack heads side by side,
# [BH / pack, d, pack T]; a query sees the keys of its own).
# The score tiles are [keys, queries], as the fused backward's above: max,
# 1 / sum and delta are rows [1, queries] that broadcast along sublanes as
# stored, the reductions run down sublanes, and o^T = v^T p^T, dq^T, dk^T,
# dv^T come out of the MXU in the operands' own layout.
# The precision contract is the flash kernels': operands reach the MXU in
# their own dtype, scores, max and sum are f32 and the scores never leave
# VMEM.
# ---------------------------------------------------------------------------
def _bdot(a, b, dims):
    """[G, ., .] x [G, ., .] contracted over axes `dims` (a's, b's), one
    product a head, f32 accumulation."""
    return jax.lax.dot_general(a, b, (((dims[0],), (dims[1],)), ((0,), (0,))),
                               preferred_element_type=jnp.float32)


def _short_keep(n, seq, causal):
    """The [1, n, n] mask of a [keys, queries] tile that holds n // seq
    sequences of `seq` positions side by side: a query sees its own
    sequence's keys, those at or before it under `causal`.  None where
    every score is kept."""
    if not causal and seq == n:
        return None
    ki = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, n, n), 2)
    keep = (qi >= ki) if causal else None  # within a sequence, as across
    if seq != n:
        same = (qi // seq) == (ki // seq)
        keep = same if keep is None else keep & same
    return keep


def _short_scores(qt, kt, kb_ref, causal, scale, seq):
    """The masked f32 scores [G, keys, queries] of q^T, k^T [G, d, n]."""
    g, _, n = qt.shape
    st = _bdot(kt, qt, (1, 1)) * scale
    if kb_ref is not None:
        st = st + kb_ref[...].reshape(g, n, 1)
    keep = _short_keep(n, seq, causal)
    if keep is not None:
        st = jnp.where(keep, st, NEG_INF)
    return st


def _short_fwd_kernel(*refs, causal, scale, has_kb, seq):
    qt_ref, kt_ref, vt_ref = refs[:3]
    kb_ref = refs[3] if has_kb else None
    ot_ref, stat_ref = refs[-2:]
    vt = vt_ref[...]
    st = _short_scores(qt_ref[...], kt_ref[...], kb_ref, causal, scale, seq)
    m = jnp.max(st, axis=1, keepdims=True)  # [G, 1, queries]
    pt = jnp.exp(st - m)
    l = jnp.sum(pt, axis=1, keepdims=True)  # >= 1: the row's max is in it
    # the max and 1 / sum as two rows, not their lse: beside a bias of -1e9
    # float32 cannot hold m + log l, and a fully padded row's probabilities
    # would come back n times too large in the backward
    stat_ref[:, 0:1, :] = m
    stat_ref[:, 1:2, :] = 1.0 / l
    ot_ref[...] = (_bdot(vt, pt.astype(vt.dtype), (2, 1)) / l).astype(
        ot_ref.dtype)


def _short_bwd_kernel(*refs, causal, scale, has_kb, seq):
    refs = list(refs)
    qt_ref, kt_ref, vt_ref = refs[:3]
    del refs[:3]
    kb_ref = refs.pop(0) if has_kb else None
    ot_ref, dot_ref, stat_ref, dqt_ref, dkt_ref, dvt_ref = refs[:6]
    dkb_ref = refs[6] if has_kb else None
    qt, kt, dot = qt_ref[...], kt_ref[...], dot_ref[...]
    g, _, n = qt.shape
    st = _short_scores(qt, kt, kb_ref, causal, scale, seq)
    # max, 1 / sum, delta: [G, 1, queries]
    pt = jnp.exp(st - stat_ref[:, 0:1, :]) * stat_ref[:, 1:2, :]
    delta = jnp.sum(dot.astype(jnp.float32) * ot_ref[...].astype(jnp.float32),
                    axis=1, keepdims=True)
    dvt_ref[...] = _bdot(dot, pt.astype(dot.dtype), (2, 2)).astype(
        dvt_ref.dtype)
    dst = pt * (_bdot(vt_ref[...], dot, (1, 1)) - delta)
    if has_kb:
        dkb_ref[...] = jnp.sum(dst, axis=2).reshape(g, 1, n)
    dsc = dst.astype(qt.dtype)
    dkt_ref[...] = (scale * _bdot(qt, dsc, (2, 2))).astype(dkt_ref.dtype)
    dqt_ref[...] = (scale * _bdot(kt, dsc, (2, 1))).astype(dqt_ref.dtype)


# The tile plan, from the shapes alone: constants from one sweep on a v5e
# (tools/attention_sweep.py --short; the table is in CHANGES.md, PR 62:
# forward + backward of one layer alone, bf16, key bias, from q, k, v stored
# [BH, T, d]).  heads: tiles a grid step holds, as many as keep the f32
# tiles a step's backward holds live (scores, probabilities, dp, ds and two
# narrowed copies: ~4.5 f32 tiles; the operand blocks are [d, n], a
# fraction of one) inside the scoped VMEM every kernel here asks for
# (_mosaic_params) with room for the compiler's own temporaries: BH 1024 x
# T 256 x 64 reads 2.11 ms at one head a step, 1.14 at 4, 1.06 at 8 (what
# the budget gives), 1.04 at 16, against dense's 2.85 and the blockwise
# kernel's 3.54 at one 256-block; T 128 0.56 at 32 (dense 1.30); T 384 1.11
# at 4 (dense 4.04, blockwise 3.22); 128-wide heads at T 256 1.21 at 8
# (dense 1.31, blockwise 1.29).
_SHORT_VMEM_BYTES = 20 * 2 ** 20


def _short_pack(t):
    """Heads a tile holds side by side: a sequence under the 128 lanes
    shares them (BH 4096 x T 64 x 64: 1.92 ms at two heads a tile, 2.43 at
    one, 2.37 at four, dense 3.17)."""
    return max(1, 128 // t)


def _divisor_up_to(n, most):
    """The largest divisor of n that is at most `most` (1 at the least)."""
    return next(x for x in range(max(1, min(most, n)), 0, -1) if n % x == 0)


class _ShortPlan(NamedTuple):
    pack: int
    heads: int


def _short_plan(bh, t, d, dv, itemsize):
    """A function of the shapes and the operands' dtype alone."""
    pack = _short_pack(t)
    while bh % pack:
        pack -= 1
    n = pack * t
    lanes = -(-n // 128) * 128
    a_tile = (18 * n * lanes  # 4.5 f32 [n, n] tiles
              + 2 * itemsize * lanes * (5 * d + 4 * dv)  # the blocks, twice
              + 2 * 4 * 8 * 4 * lanes)  # kb, max | 1 / sum, dkb rows
    most = max(1, _SHORT_VMEM_BYTES // a_tile)
    return _ShortPlan(pack, _divisor_up_to(bh // pack, most))


def _short_t(x, pack):
    """[BH, T, d] -> [BH / pack, d, pack T]: transposed, `pack` heads side
    by side."""
    bh, t, d = x.shape
    return x.reshape(bh // pack, pack, t, d).transpose(0, 3, 1, 2).reshape(
        bh // pack, d, pack * t)


def _short_t_back(xt, pack):
    """_short_t's inverse."""
    tiles, d, n = xt.shape
    return xt.reshape(tiles, d, pack, n // pack).transpose(0, 2, 3, 1).reshape(
        tiles * pack, n // pack, d)


def _short_operands(plan, qt, kt, vt, kb):
    """((q / k, v, one-row and two-row block specs), the in_specs and the
    operands both kernels start with: q^T, k^T, v^T and the bias rows where
    there are any)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(rows):
        return pl.BlockSpec((plan.heads, rows, qt.shape[2]),
                            lambda i: (i, 0, 0), memory_space=pltpu.VMEM)

    qk_spec, v_spec, row_spec, stat_spec = (
        spec(qt.shape[1]), spec(vt.shape[1]), spec(1), spec(2))
    in_specs, args = [qk_spec, qk_spec, v_spec], [qt, kt, vt]
    if kb is not None:
        in_specs.append(row_spec)
        args.append(kb)
    return (qk_spec, v_spec, row_spec, stat_spec), in_specs, args


# jitted at module level as the flash entries above: a model's attentions of
# one shape and configuration trace each body once
_SHORT_STATICS = ("causal", "scale", "plan", "interpret")


@functools.partial(jax.jit, static_argnames=_SHORT_STATICS)
def _short_fwd_call(q, k, v, kbias, *, causal, scale, plan, interpret):
    """q, k: [BH, T, d], v: [BH, T, dv], kbias: [BH, T] f32 or None.
    Returns (o, what the backward reads: q^T, k^T, v^T, the bias rows, o^T
    and the [BH / pack, 2, pack T] max | 1 / sum rows, all as the kernels
    take them)."""
    from jax.experimental import pallas as pl

    t = q.shape[1]
    qt, kt, vt = (_short_t(x, plan.pack) for x in (q, k, v))
    tiles, _, n = qt.shape
    kb = None if kbias is None else kbias.reshape(tiles, 1, n)
    (_, v_spec, row_spec, stat_spec), in_specs, args = _short_operands(
        plan, qt, kt, vt, kb)
    ot, stat = pl.pallas_call(
        functools.partial(_short_fwd_kernel, causal=causal, scale=scale,
                          has_kb=kb is not None, seq=t),
        grid=(tiles // plan.heads,),
        in_specs=in_specs,
        out_specs=[v_spec, stat_spec],
        out_shape=[_sds(vt.shape, q.dtype, q, k, v),
                   _sds((tiles, 2, n), jnp.float32, q, k, v)],
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*args)
    return _short_t_back(ot, plan.pack), (qt, kt, vt, kb, ot, stat)


@functools.partial(jax.jit, static_argnames=_SHORT_STATICS)
def _short_bwd_call(res, do, *, causal, scale, plan, interpret):
    """(dq, dk, dv, dkbias [BH, T] f32 or None): one pallas_call."""
    from jax.experimental import pallas as pl

    qt, kt, vt, kb, ot, stat = res
    t = do.shape[1]
    dot = _short_t(do, plan.pack)
    (qk_spec, v_spec, row_spec, stat_spec), in_specs, args = _short_operands(
        plan, qt, kt, vt, kb)
    in_specs += [v_spec, v_spec, stat_spec]
    args += [ot, dot, stat]
    out_specs = [qk_spec, qk_spec, v_spec]
    out_shape = [_sds(qt.shape, qt.dtype, qt, kt, vt, do),
                 _sds(kt.shape, kt.dtype, qt, kt, vt, do),
                 _sds(vt.shape, vt.dtype, qt, kt, vt, do)]
    if kb is not None:
        out_specs.append(row_spec)
        out_shape.append(_sds(kb.shape, jnp.float32, qt, kt, vt, do))
    outs = pl.pallas_call(
        functools.partial(_short_bwd_kernel, causal=causal, scale=scale,
                          has_kb=kb is not None, seq=t),
        grid=(qt.shape[0] // plan.heads,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=_mosaic_params(),
        interpret=interpret,
    )(*args)
    dq, dk, dv = (_short_t_back(x, plan.pack) for x in outs[:3])
    return dq, dk, dv, (outs[3].reshape(-1, t) if kb is not None else None)


def _short_statics(bh, t, d, dv, dtype, causal, scale):
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    return dict(causal=bool(causal), scale=float(scale),
                plan=_short_plan(bh, t, d, dv, jnp.dtype(dtype).itemsize),
                interpret=_interpret())


# ---------------------------------------------------------------------------
# the same one tile, read where the projections wrote it
#
# A projection's matmul writes [B, T, H d]: H d lanes, whole 128-lane tiles,
# no padding.  The kernels above reach their [BH / pack, d, pack T] from it
# in two transposing copies an operand at T = 64 (35.0 ms of a 175.6 ms step
# of tfm_base_train_s64, PERF.md section 5, PR 62).  These index that array
# as it is: a block is (seqs, T, H d) on the batch axis, and a head is a
# static slice of the lanes.  Heads of 64 sit two to a 128-lane tile, and a
# step takes such a GROUP of heads in one product with no lane moved: the
# queries (and dO) are laid out block-diagonally ([group T, group d], a
# head's rows keep its own d lanes and zeros elsewhere: _diag), so
# k [T, group d] x that^T is [keys, group T queries], each head's scores
# side by side in full 128 lanes, and the products that follow (dv, dk
# against the same block-diagonal operand) land in the operands' own lanes.
# Only the two products that contract over the KEYS of a [keys, queries]
# tile (o and dq: the tile's major dim on both sides) come out [group T,
# group d] with the heads' blocks on the diagonal; _undiag picks each head's
# lanes from its own rows.
# The tiles are [keys, queries] as above: max, 1 / sum and delta are rows,
# the reductions run down sublanes.  delta is sum_k p dp, the softmax's own
# Jacobian, in f32: the backward reads no o.
# ---------------------------------------------------------------------------
class _InPlacePlan(NamedTuple):
    seqs: int  # sequences a grid step
    group: int  # heads a product: their d lanes side by side
    unroll: int  # sequences a loop body holds, batched


# (sequence, group of heads) pairs a loop body holds, the sequences batched
# in every product: each pair is a chain of products and reductions that
# waits on itself, and the compiler fills one chain's waits with the others'
# work.  From the sweep on a v5e (tools/attention_sweep.py --short
# --in-place; CHANGES.md, PR 63; forward + backward of one layer from
# [B, T, H d], bf16, causal, key bias): B 512 x T 64, 16 sequences a step: 4
# pairs a body 1.123 ms, 8 0.728, 16 0.576, 32 0.528, 64 0.524 (PR 62's
# kernel behind its copies 2.164, dense 3.144); B 128 x T 256, 4 sequences a
# step: 4 pairs 0.942, 8 0.878, 16 0.871 (1.691, 3.049).  32 is also the
# most tiles PR 62's kernel holds a step.  (The same bodies unrolled in
# Python, one sequence a product, read 0.790 at 32 and cost a first step 5 s
# of tracing and lowering.)
_INPLACE_CHAINS = 32


def _inplace_plan(b, t, h, d, dv, itemsize):
    """A function of the shapes and the operands' dtype alone: as many heads
    a product as fill 128 lanes (and divide H), as many sequences a step as
    keep the backward's seven blocks, twice, and the live f32 tiles of one
    group inside _SHORT_VMEM_BYTES (B 512 x T 64 x 8 heads of 64: 16; 2 to
    32 a step read within 2 % of each other), as many of them a loop body as
    make _INPLACE_CHAINS chains."""
    group = next(g for g in range(max(1, 128 // max(d, dv)), 0, -1)
                 if h % g == 0)
    a_seq = 2 * itemsize * t * h * (4 * d + 3 * dv)
    tiles = 6 * 4 * t * max(128, group * t)
    most = max(1, (_SHORT_VMEM_BYTES - tiles) // a_seq)
    seqs = _divisor_up_to(b, most)
    return _InPlacePlan(seqs, group, _divisor_up_to(
        seqs, _INPLACE_CHAINS // (h // group)))


def _inplace_loop(plan, some_sequences):
    """some_sequences(rows) for every plan.unroll sequences of the block:
    one batched body (the compiler schedules its independent chains
    against each other), a loop over the bodies."""
    from jax.experimental import pallas as pl

    def body(i, carry):
        some_sequences(pl.ds(i * plan.unroll, plan.unroll))
        return carry

    jax.lax.fori_loop(0, plan.seqs // plan.unroll, body, 0)


def _lane_head(shape, width):
    """int32 of `shape`: which head of the group a lane belongs to."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, 2) // width


def _diag(x, group, width):
    """[S, T, group width] -> [S, group T, group width]: head g's rows keep
    its own lanes, zeros elsewhere."""
    if group == 1:
        return x
    head = _lane_head(x.shape, width)
    return jnp.concatenate(
        [jnp.where(head == g, x, jnp.zeros_like(x)) for g in range(group)],
        axis=1)


def _undiag(r, group, width):
    """_diag's reading: [S, group T, group width] -> [S, T, group width],
    head g's lanes from its own rows."""
    if group == 1:
        return r
    t = r.shape[1] // group
    head = _lane_head((r.shape[0], t, r.shape[2]), width)
    out = r[:, :t]
    for g in range(1, group):
        out = jnp.where(head == g, r[:, g * t:(g + 1) * t], out)
    return out


def _inplace_scores(k, qd, kb, keep, scale):
    """The masked f32 scores [S, keys, group T queries] of k [S, T, group d]
    against the block-diagonal queries; kb the keys' bias as columns."""
    st = _bdot(k, qd, (2, 2)) * scale
    if kb is not None:
        st = st + kb
    if keep is not None:
        st = jnp.where(keep, st, NEG_INF)
    return st


def _inplace_keep(t, group, causal):
    """[1, keys, group T queries]: a query sees the keys at or before it."""
    if not causal:
        return None
    ki = jax.lax.broadcasted_iota(jnp.int32, (1, t, group * t), 1)
    qi = jax.lax.broadcasted_iota(jnp.int32, (1, t, group * t), 2) % t
    return qi >= ki


def _inplace_fwd_kernel(*refs, causal, scale, has_kb, plan, heads):
    q_ref, k_ref, v_ref = refs[:3]
    kb_ref = refs[3] if has_kb else None
    o_ref, stat_ref = refs[-2:]
    t, group = q_ref.shape[1], plan.group
    d, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    keep = _inplace_keep(t, group, causal)

    def some_sequences(b):
        kb = kb_ref[b].reshape(plan.unroll, t, 1) if has_kb else None
        for j in range(heads // group):
            qk = slice(j * group * d, (j + 1) * group * d)
            vs = slice(j * group * dv, (j + 1) * group * dv)
            v = v_ref[b, :, vs]
            st = _inplace_scores(k_ref[b, :, qk],
                                 _diag(q_ref[b, :, qk], group, d), kb, keep,
                                 scale)
            m = jnp.max(st, axis=1, keepdims=True)  # [S, 1, group T]
            p = jnp.exp(st - m)
            linv = 1.0 / jnp.sum(p, axis=1, keepdims=True)  # sum >= 1
            # the max and 1 / sum as two rows, not their lse (see
            # _short_fwd_kernel)
            stat_ref[b, 2 * j:2 * j + 1, :] = m
            stat_ref[b, 2 * j + 1:2 * j + 2, :] = linv
            o_ref[b, :, vs] = _undiag(
                _bdot((p * linv).astype(v.dtype), v, (1, 1)), group,
                dv).astype(o_ref.dtype)

    _inplace_loop(plan, some_sequences)


def _inplace_bwd_kernel(*refs, causal, scale, has_kb, plan, heads):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    del refs[:3]
    kb_ref = refs.pop(0) if has_kb else None
    do_ref, stat_ref, dq_ref, dk_ref, dv_ref = refs[:5]
    dkb_ref = refs[5] if has_kb else None
    t, group = q_ref.shape[1], plan.group
    d, dv = q_ref.shape[2] // heads, v_ref.shape[2] // heads
    keep = _inplace_keep(t, group, causal)

    def some_sequences(b):
        kb = kb_ref[b].reshape(plan.unroll, t, 1) if has_kb else None
        dst_sum = None
        for j in range(heads // group):
            qk = slice(j * group * d, (j + 1) * group * d)
            vs = slice(j * group * dv, (j + 1) * group * dv)
            k, v = k_ref[b, :, qk], v_ref[b, :, vs]
            qd = _diag(q_ref[b, :, qk], group, d)
            dod = _diag(do_ref[b, :, vs], group, dv)
            st = _inplace_scores(k, qd, kb, keep, scale)
            p = (jnp.exp(st - stat_ref[b, 2 * j:2 * j + 1, :])
                 * stat_ref[b, 2 * j + 1:2 * j + 2, :])
            dv_ref[b, :, vs] = _bdot(p.astype(v.dtype), dod, (2, 1)).astype(
                dv_ref.dtype)
            dp = _bdot(v, dod, (2, 2))
            dst = p * (dp - jnp.sum(p * dp, axis=1, keepdims=True))
            if has_kb:
                dst_sum = dst if dst_sum is None else dst_sum + dst
            dsc = dst.astype(k.dtype)
            dk_ref[b, :, qk] = (scale * _bdot(dsc, qd, (2, 1))).astype(
                dk_ref.dtype)
            dq_ref[b, :, qk] = (scale * _undiag(
                _bdot(dsc, k, (1, 1)), group, d)).astype(dq_ref.dtype)
        if has_kb:
            dkb_ref[b] = jnp.sum(dst_sum, axis=2).reshape(plan.unroll, 1, t)

    _inplace_loop(plan, some_sequences)


def _inplace_call(kernel, plan, interpret, args, outs):
    """One pallas_call over the batch axis: every operand and every result
    ([B, ., .] arrays; `outs` their ShapeDtypeStructs) in blocks of
    plan.seqs sequences."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def spec(x):
        return pl.BlockSpec((plan.seqs,) + tuple(x.shape[1:]),
                            lambda i: (i, 0, 0), memory_space=pltpu.VMEM)

    return pl.pallas_call(
        kernel, grid=(args[0].shape[0] // plan.seqs,),
        in_specs=[spec(x) for x in args], out_specs=[spec(x) for x in outs],
        out_shape=outs, compiler_params=_mosaic_params(),
        interpret=interpret)(*args)


_INPLACE_STATICS = ("heads", "causal", "scale", "plan", "interpret")


@functools.partial(jax.jit, static_argnames=_INPLACE_STATICS)
def _inplace_fwd_call(q, k, v, kbias, *, heads, causal, scale, plan,
                      interpret):
    """q, k: [B, T, H d], v: [B, T, H dv], kbias: [B, T] f32 or None.
    Returns (o [B, T, H dv], what the backward reads: q, k, v, the bias
    rows [B, 1, T] and the [B, 2 H / group, group T] max | 1 / sum
    rows)."""
    b, t, _ = q.shape
    kb = None if kbias is None else kbias.reshape(b, 1, t)
    o, stat = _inplace_call(
        functools.partial(_inplace_fwd_kernel, causal=causal, scale=scale,
                          has_kb=kb is not None, plan=plan, heads=heads),
        plan, interpret, [x for x in (q, k, v, kb) if x is not None],
        [_sds(v.shape, q.dtype, q, k, v),
         _sds((b, 2 * (heads // plan.group), plan.group * t), jnp.float32,
              q, k, v)])
    return o, (q, k, v, kb, stat)


@functools.partial(jax.jit, static_argnames=_INPLACE_STATICS)
def _inplace_bwd_call(res, do, *, heads, causal, scale, plan, interpret):
    """(dq, dk, dv, dkbias [B, T] f32 or None): one pallas_call."""
    q, k, v, kb, stat = res
    grads = [q, k, v] + ([] if kb is None else [kb])
    outs = _inplace_call(
        functools.partial(_inplace_bwd_kernel, causal=causal, scale=scale,
                          has_kb=kb is not None, plan=plan, heads=heads),
        plan, interpret, grads + [do, stat],
        [_sds(x.shape, x.dtype, q, k, v, do) for x in grads])
    return outs[0], outs[1], outs[2], (
        outs[3].reshape(q.shape[:2]) if kb is not None else None)


def _inplace_statics(q, v, heads, causal, scale):
    b, t, hd = q.shape
    d, dv = hd // heads, v.shape[2] // heads
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    return dict(heads=int(heads), causal=bool(causal), scale=float(scale),
                plan=_inplace_plan(b, t, heads, d, dv,
                                   jnp.dtype(q.dtype).itemsize),
                interpret=_interpret())


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def short_attention(q, k, v, kbias=None, causal=False, scale=None,
                    heads=None):
    """flash_attention's one-tile form for a short self-attention, q, k:
    [BH, T, d], v: [BH, T, dv] -> [BH, T, dv], kbias: optional [BH, T]
    additive key bias: the whole sequence is one tile and a grid step holds
    several heads (_short_plan), so a head pays no grid step of its own,
    and the kernels read and write [BH, d, T], the sequence in the lanes.
    With `heads` the operands are the projections' own arrays, q, k:
    [B, T, H d], v: [B, T, H dv] -> [B, T, H dv], kbias: [B, T], and the
    kernels read and write them in place (_inplace_plan): no copy of an
    operand on either side.
    The same arithmetic at the same precision as flash_attention (f32
    scores and softmax statistics, MXU operands in their own dtype), no
    [BH, T, T] array in HBM; the backward is one kernel and rebuilds the
    probabilities from each query's saved max and 1 / sum."""
    return _short_vjp_fwd(q, k, v, kbias, causal, scale, heads)[0]


def _short_vjp_fwd(q, k, v, kbias, causal, scale, heads):
    if heads is not None:
        return _inplace_fwd_call(q, k, v, kbias, **_inplace_statics(
            q, v, heads, causal, scale))
    return _short_fwd_call(q, k, v, kbias, **_short_statics(
        *q.shape, v.shape[2], q.dtype, causal, scale))


def _short_vjp_bwd(causal, scale, heads, res, do):
    if heads is not None:
        return _inplace_bwd_call(res, do, **_inplace_statics(
            res[0], res[2], heads, causal, scale))
    qt = res[0]  # [BH / pack, d, pack T]
    return _short_bwd_call(res, do, **_short_statics(
        do.shape[0], do.shape[1], qt.shape[1], do.shape[2], qt.dtype, causal,
        scale))


short_attention.defvjp(_short_vjp_fwd, _short_vjp_bwd)
