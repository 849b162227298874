"""Trace-time attribution counters of the op lowerings (`note_*` /
`attribution()`): per-family pallas-hit counts, hand-written VJPs, random
draws by generator, uneven weight constraints, `moe_ffn`'s live chunks, the
windowed flash kernels' band grids, the causal flash kernels' tiles by class
and `kda_attention`'s chunking
(chip_smoke.py and the benchmark's
readers read them), so an MFU regression can be pinned to "kernel X
stopped dispatching" instead of guessed at.  Counts tick at TRACE time
(once per compiled program, not per step) — they attribute what the
compiled step contains, not how often it runs.
"""

import threading

__all__ = [
    "note_kernel",
    "note_dense_vjp",
    "attribution",
    "reset_attribution",
]

_lock = threading.RLock()
_kernel_hits = {}  # family -> pallas dispatch count (trace-time)
_dense_vjp_hits = {}  # family -> hand-written plain-XLA VJP engagements
_rng_draws = {}  # generator ("rbg" / "threefry") -> draw sites traced
_uneven_constraints = {}  # op type -> uneven weight constraints placed
# moe_ffn lowerings that ran a share's row work over the live chunks
_moe_live_chunks = {"ops": 0, "chunk_rows": {}}  # chunk_rows: N*k -> rows a chunk
# windowed fused_attention lowerings that took the flash kernel, with the
# forward grid steps a head walks and the tiles its band computes
_attention_band_grid = {"ops": 0, "steps": {}}  # "TxWxBQxBK" -> [walked, computed]
# causal fused_attention lowerings that took the flash kernel, with what a
# head's forward and backward bodies compute of each shape
# "TxWxBQxBKxD" -> {"ops", "visible", "fwd_pairs", "bwd_pairs",
#                   "fwd_bodies", "bwd_bodies", "tiles": {class: tiles}}
_attention_tile_classes = {"ops": 0, "shapes": {}}
# kda_attention lowerings (a grad op lowers its forward again), with the
# chunking each length got
# T -> [chunk, chunks a grid step, T, padded T, heads a carry step]
_kda_chunks = {"ops": 0, "lengths": {}}
# gated_delta_attention lowerings, alike: the family's member whose decay
# is one number a head
_gdn_chunks = {"ops": 0, "lengths": {}}


def note_kernel(family, n=1):
    """Count a pallas dispatch for `family` (attention, grouped_matmul).
    Trace-time counter."""
    with _lock:
        _kernel_hits[family] = _kernel_hits.get(family, 0) + n


def note_dense_vjp(family):
    """Count a trace-time engagement of a hand-written VJP in plain XLA
    ops (no Mosaic call: not a pallas hit) for `family`."""
    with _lock:
        _dense_vjp_hits[family] = _dense_vjp_hits.get(family, 0) + 1


def note_rng_draw(impl):
    """Count a trace-time request of a key by a randomness-consuming op
    (LowerCtx.rng), by the generator the key draws from."""
    with _lock:
        _rng_draws[impl] = _rng_draws.get(impl, 0) + 1


def note_uneven_constraint(op_type):
    """Count a trace-time sharding constraint that a lowering placed on a
    weight stored replicated but computed in uneven shards
    (PartitionRules.compute_spec_for), by the op type that placed it."""
    with _lock:
        _uneven_constraints[op_type] = _uneven_constraints.get(op_type, 0) + 1


def note_live_chunks(rows, chunk_rows):
    """Count a trace-time lowering of a `moe_ffn` that holds a share of
    its experts, whose row work runs over the live chunks of its `rows`
    (N k) row buffers, and keep the rows of a chunk it chose."""
    with _lock:
        _moe_live_chunks["ops"] += 1
        _moe_live_chunks["chunk_rows"][int(rows)] = int(chunk_rows)


def note_band_grid(t, window, block_q, block_k, walked, computed):
    """Count a trace-time lowering of a windowed `fused_attention` to the
    flash kernel, and keep by shape the grid steps a head's forward walks
    and the tiles its band lets compute."""
    with _lock:
        _attention_band_grid["ops"] += 1
        _attention_band_grid["steps"]["%dx%dx%dx%d" % (
            t, window, block_q, block_k)] = [int(walked), int(computed)]


def note_tile_classes(t, window, block_q, block_k, d, stats):
    """Count a trace-time lowering of a causal `fused_attention` to the
    flash kernel, and keep by shape what pallas_kernels.tile_class_stats
    says of it (the score pairs visible, the pairs a head's forward and
    backward bodies compute, the copies of the tile's computation a body
    holds, the tiles by class, the inner blocks a head's forward and
    backward walks copy in) with the lowerings of that shape."""
    key = "%dx%dx%dx%dx%d" % (t, window, block_q, block_k, d)
    with _lock:
        _attention_tile_classes["ops"] += 1
        ops = _attention_tile_classes["shapes"].get(key, {}).get("ops", 0)
        _attention_tile_classes["shapes"][key] = dict(
            stats, tiles=dict(stats["tiles"]), ops=ops + 1)


def note_kda_chunks(t, padded_t, chunk, block, carry_heads,
                    decay="channel"):
    """Count a trace-time lowering of a delta-rule op, and keep by length
    the chunk it ran at, the chunks a grid step of its inside's kernels
    holds, the length it padded to and the heads a grid step of its
    carry's kernels holds.  `decay`: "channel" (`kda_attention`) or "head"
    (`gated_delta_attention`): which record it lands in."""
    record = {"channel": _kda_chunks, "head": _gdn_chunks}[decay]
    with _lock:
        record["ops"] += 1
        record["lengths"][int(t)] = [int(chunk), int(block), int(t),
                                     int(padded_t), int(carry_heads)]


def attribution():
    """Snapshot for bench attribution: per-family pallas-hit counts,
    in-program random draws by generator, uneven weight constraints by op
    type, the moe_ffn lowerings that took the live-chunk path with the
    rows of a chunk by buffer size, the windowed flash lowerings with their
    forward grid steps walked and computed by shape, the causal flash
    lowerings with the pairs their bodies compute, the tiles of each
    class and the blocks their walks copy in by shape
    (`attention_tile_classes`), the kda_attention
    lowerings with [chunk, chunks a grid step, T, padded T, heads a carry
    step] by length and the gated_delta_attention lowerings alike
    (`gdn_chunks`, "decay": "head")."""
    with _lock:
        return {
            "pallas_hits": dict(_kernel_hits),
            "dense_vjp_hits": dict(_dense_vjp_hits),
            "rng_draws": {"rbg": 0, "threefry": 0, **_rng_draws},
            "uneven_constraints": dict(_uneven_constraints),
            "moe_live_chunks": {
                "ops": _moe_live_chunks["ops"],
                "chunk_rows": dict(_moe_live_chunks["chunk_rows"])},
            "attention_band_grid": {
                "ops": _attention_band_grid["ops"],
                "steps": {k: list(v) for k, v in
                          _attention_band_grid["steps"].items()}},
            "attention_tile_classes": {
                "ops": _attention_tile_classes["ops"],
                "shapes": {k: dict(v, tiles=dict(v["tiles"])) for k, v in
                           _attention_tile_classes["shapes"].items()}},
            "kda_chunks": {
                "ops": _kda_chunks["ops"],
                "lengths": {k: list(v) for k, v in
                            _kda_chunks["lengths"].items()}},
            "gdn_chunks": {
                "ops": _gdn_chunks["ops"], "decay": "head",
                "lengths": {k: list(v) for k, v in
                            _gdn_chunks["lengths"].items()}},
        }


def reset_attribution():
    with _lock:
        _kernel_hits.clear()
        _dense_vjp_hits.clear()
        _rng_draws.clear()
        _uneven_constraints.clear()
        _moe_live_chunks.update(ops=0, chunk_rows={})
        _attention_band_grid.update(ops=0, steps={})
        _attention_tile_classes.update(ops=0, shapes={})
        _kda_chunks.update(ops=0, lengths={})
        _gdn_chunks.update(ops=0, lengths={})
