"""The trace-time attribution counters (ops/kernel_tuning.py): what the
benchmark's readers and chip_smoke.py read through `attribution()`, and
that `reset_attribution()` clears it."""

import pytest

from paddle_tpu.ops import kernel_tuning as kt


@pytest.fixture(autouse=True)
def _fresh_counters():
    kt.reset_attribution()
    yield
    kt.reset_attribution()


def test_attribution_counters_and_reset():
    kt.note_kernel("attention")
    kt.note_kernel("attention")
    kt.note_kernel("xent")
    att = kt.attribution()
    assert att["pallas_hits"] == {"attention": 2, "xent": 1}
    kt.reset_attribution()
    att = kt.attribution()
    assert att["pallas_hits"] == {}


def test_band_grid_attribution_and_reset():
    """note_band_grid counts a lowering and keeps [walked, computed] by
    T x window x block_q x block_k; reset clears both."""
    kt.reset_attribution()
    assert kt.attribution()["attention_band_grid"] == {"ops": 0, "steps": {}}
    kt.note_band_grid(8192, 2048, 1024, 1024, 24, 21)
    kt.note_band_grid(8192, 2048, 1024, 1024, 24, 21)
    kt.note_band_grid(4096, 512, 512, 512, 16, 15)
    assert kt.attribution()["attention_band_grid"] == {
        "ops": 3, "steps": {"8192x2048x1024x1024": [24, 21],
                            "4096x512x512x512": [16, 15]}}
    kt.reset_attribution()
    assert kt.attribution()["attention_band_grid"] == {"ops": 0, "steps": {}}


def _stats(visible, fwd, bwd, bodies=(5, 5), tiles=None, fetches=(9, 9)):
    return {"visible": visible, "fwd_pairs": fwd, "bwd_pairs": bwd,
            "fwd_bodies": bodies[0], "bwd_bodies": bodies[1],
            "tiles": tiles or {"whole": 6, "diag": 4, "edge": 0, "both": 0},
            "fwd_fetches": fetches[0], "bwd_fetches": fetches[1]}


def test_tile_class_attribution_and_reset():
    """note_tile_classes counts a lowering and keeps, by T x window x
    block_q x block_k x d, what tile_class_stats said of the shape and how
    many lowerings had it; reset clears both; a snapshot is a copy."""
    empty = {"ops": 0, "shapes": {}}
    assert kt.attribution()["attention_tile_classes"] == empty
    said = _stats(8390656, 8912896, 8912896)
    kt.note_tile_classes(4096, 0, 1024, 1024, 128, said)
    kt.note_tile_classes(4096, 0, 1024, 1024, 128, said)
    kt.note_tile_classes(1024, 0, 1024, 1024, 64,
                         _stats(524800, 655360, 655360, (4, 4)))
    got = kt.attribution()["attention_tile_classes"]
    assert got["ops"] == 3
    assert got["shapes"]["4096x0x1024x1024x128"] == dict(said, ops=2)
    assert got["shapes"]["1024x0x1024x1024x64"]["ops"] == 1
    got["shapes"]["4096x0x1024x1024x128"]["tiles"]["whole"] = 0
    assert kt.attribution()["attention_tile_classes"]["shapes"][
        "4096x0x1024x1024x128"]["tiles"]["whole"] == 6
    kt.reset_attribution()
    assert kt.attribution()["attention_tile_classes"] == empty


def test_the_lowering_records_the_tile_classes_of_a_causal_flash_op_only():
    """attribution()["attention_tile_classes"], at trace time, by the op's
    lowering: Trinity-Mini's two kinds of layer at T 8192 in blocks of
    1024, one record a shape with its lowerings; a non-causal op and the
    dense lowering (a CPU-placed step) record none."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.registry import LowerCtx
    from paddle_tpu.ops import nn_ops
    from paddle_tpu.ops import pallas_kernels as pk

    def op(ctx, q, k, v, **attrs):
        return nn_ops._fused_attention(
            ctx, {"Q": [q], "K": [k], "V": [v]}, attrs)["Out"][0]

    tpu = LowerCtx(platform="tpu")
    x = jax.ShapeDtypeStruct((1, 2, 8192, 128), jnp.bfloat16)
    for window, times in ((2048, 4), (0, 1)):
        for _ in range(times):
            jax.eval_shape(lambda q, k, v: op(tpu, q, k, v, causal=True,
                                              window=window), x, x, x)
    got = kt.attribution()["attention_tile_classes"]
    assert got["ops"] == 5 and sorted(got["shapes"]) == [
        "8192x0x1024x1024x128", "8192x2048x1024x1024x128"]
    band = got["shapes"]["8192x2048x1024x1024x128"]
    assert band == dict(pk.tile_class_stats(8192, 128, 1024, 1024, 2048),
                        ops=4)
    assert band["tiles"] == {"whole": 7, "diag": 8, "edge": 6, "both": 0}
    assert (band["fwd_fetches"], band["bwd_fetches"]) == (20, 20)
    full = got["shapes"]["8192x0x1024x1024x128"]
    assert (full["ops"], full["fwd_fetches"], full["bwd_fetches"]) == (
        1, 35, 35)  # 36 tiles, one of them on a block held from the row before
    kt.reset_attribution()
    jax.eval_shape(lambda q, k, v: op(tpu, q, k, v, causal=False), x, x, x)
    jax.eval_shape(lambda q, k, v: op(LowerCtx(platform="cpu"), q, k, v,
                                      causal=True), x, x, x)
    assert kt.attribution()["pallas_hits"]["attention"] == 1
    assert kt.attribution()["attention_tile_classes"] == {"ops": 0,
                                                          "shapes": {}}


def test_attention_pairs_computed_over_visible_is_in_the_benchmark_by_name(
        monkeypatch):
    """BENCHMARK.json carries `attention_pairs_computed_over_visible` for
    the nine cells that run a flash kernel and no other, its layer_metrics
    file names a reader that imports, and the reader answers None on a
    program that records no tile classes (the parent commit's, or a cell
    whose attention stays dense), the ratio weighted by lowerings
    otherwise: every tile whole would read the parent's 1.36 on
    Trinity-Mini's four window layers and one full layer."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    name = "attention_pairs_computed_over_visible"
    entry, = [m for m in spec["per_layer"] if m["name"] == name]
    # appended by PR 53, nothing moved; PR 56 appended one right after it
    # (found by name: a later PR's entries come after the two)
    names = [m["name"] for m in spec["per_layer"]]
    assert names.index("attention_block_fetches_over_tiles") == names.index(
        name) + 1
    dense = {"tfm_base_train", "tfm_base_train_s64", "resnet50_train"}
    assert set(entry["workloads"]) == {
        c["name"] for c in spec["workloads"]} - dense
    assert (entry["unit"], entry["better"], entry["moves"],
            entry["layer"], entry["source"]) == (
        "ratio", "lower", "train_mfu", "Op lowerings + kernels",
        "program_counter")
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        how = json.load(f)
    path = os.path.join(root, "benchmark", "readers", how["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("tile_class_stat", path)
    reader = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(reader)
    ctx = {"log": lambda msg: None}
    assert reader.read(ctx, **how.get("args", {})) is None
    before = kt.attribution()
    monkeypatch.setattr(kt, "attribution", lambda: {
        k: v for k, v in before.items() if k != "attention_tile_classes"})
    assert reader.read(ctx) is None  # a program from before the counter
    monkeypatch.undo()
    tile = 1024 * 1024
    for _ in range(8):  # four window layers, forward op and grad op
        kt.note_tile_classes(8192, 2048, 1024, 1024, 128, _stats(
            14681088, 21 * tile, 21 * tile))
    for _ in range(2):
        kt.note_tile_classes(8192, 0, 1024, 1024, 128, _stats(
            8192 * 8193 // 2, 36 * tile, 36 * tile))
    assert reader.read(ctx) == pytest.approx(1.3635, abs=1e-4)
    kt.reset_attribution()
    kt.note_tile_classes(1024, 0, 1024, 1024, 64, _stats(
        524800, tile, 655360))  # a forward whole, a backward in four strips
    assert reader.read(ctx) == pytest.approx((2 + 1.25) / 2 * 1048576
                                             / 1049600, rel=1e-9)


# T x window x d -> (tiles a head, forward fetches, backward fetches) of the
# flash cells' cores in the blocks nn_ops._flash_block answers: 1024, and
# 512 under Laguna-XS.2's 512 window (PR 66)
CELL_FETCHES = {
    (1024, 0, 64): (1, 1, 1),  # GPT-2: one block a sequence
    (4096, 0, 128): (10, 9, 9),  # Ouro, OLMoE
    (6144, 0, 192): (21, 20, 20),  # kanana-2, Kimi Linear
    (8192, 0, 64): (36, 35, 35),  # LFM2
    (8192, 0, 128): (36, 35, 35),  # Trinity-Mini's full layer
    (8192, 2048, 128): (21, 20, 20),  # its window layers, on the band grid
    (8192, 0, 256): (36, 35, 35),  # Qwen3-Next
    (6144, 0, 128): (21, 20, 20),  # Laguna-XS.2's full layers
    (6144, 512, 128): (23, 12, 12),  # its window layers: the diagonal
    # tile's block is held over for the next row's edge tile
}


@pytest.mark.parametrize("t,window,d", sorted(CELL_FETCHES))
def test_the_attribution_carries_the_fetches_at_the_cells_shapes(t, window,
                                                                 d):
    """What tile_class_stats says of a cell's core goes into the record
    whole, the two counts of PR 56 with the rest: the inner blocks a head's
    forward and backward walks copy in, each no more than the tiles the
    mask lets run (every block copied in is computed on)."""
    from paddle_tpu.ops import nn_ops, pallas_kernels as pk

    blk = nn_ops._flash_block(t, window)
    said = pk.tile_class_stats(t, d, blk, blk, window)
    kt.note_tile_classes(t, window, blk, blk, d, said)
    got, = kt.attribution()["attention_tile_classes"]["shapes"].values()
    assert got == dict(said, ops=1)
    assert (sum(got["tiles"].values()), got["fwd_fetches"],
            got["bwd_fetches"]) == CELL_FETCHES[t, window, d]


def test_attention_block_fetches_over_tiles_is_in_the_benchmark_by_name(
        monkeypatch):
    """BENCHMARK.json carries `attention_block_fetches_over_tiles` last,
    for the nine cells of `attention_pairs_computed_over_visible`; its
    layer_metrics file names a reader that imports and answers None on a
    program that records no fetches (the parent commit's record holds the
    tiles without them) or no flash lowering, and otherwise forward +
    backward fetches over twice the tiles, weighted by lowerings: 0.95 on
    kanana-2's five latent layers, 1.71 had every grid step named its own
    block, 0.96 on Trinity-Mini's four window layers and one full one."""
    import importlib.util
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    name = "attention_block_fetches_over_tiles"
    # appended right behind PR 53's entry, nothing moved (by name: a later
    # PR's entries come after it)
    (at, pairs), = [(i, m) for i, m in enumerate(spec["per_layer"])
                    if m["name"] == "attention_pairs_computed_over_visible"]
    entry = spec["per_layer"][at + 1]
    assert entry == dict(pairs, name=name)
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        how = json.load(f)
    path = os.path.join(root, "benchmark", "readers", how["reader"] + ".py")
    mod_spec = importlib.util.spec_from_file_location("block_fetch_stat",
                                                      path)
    reader = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(reader)
    ctx = {"log": lambda msg: None}
    assert reader.read(ctx, **how.get("args", {})) is None
    parent = _stats(6144 * 6145 // 2, 0, 0)
    del parent["fwd_fetches"], parent["bwd_fetches"]
    kt.note_tile_classes(6144, 0, 1024, 1024, 192, parent)
    assert reader.read(ctx) is None  # a program from before the counter
    kt.reset_attribution()
    tiles = {"whole": 15, "diag": 6, "edge": 0, "both": 0}
    for _ in range(10):  # five latent layers, forward op and grad op
        kt.note_tile_classes(6144, 0, 1024, 1024, 192, _stats(
            1, 1, 1, tiles=tiles, fetches=(20, 20)))
    assert reader.read(ctx) == pytest.approx(40 / 42.0)
    kt.reset_attribution()
    kt.note_tile_classes(6144, 0, 1024, 1024, 192, _stats(
        1, 1, 1, tiles=tiles, fetches=(36, 36)))
    assert reader.read(ctx) == pytest.approx(36 / 21.0)
    kt.reset_attribution()
    for _ in range(8):
        kt.note_tile_classes(8192, 2048, 1024, 1024, 128, _stats(
            1, 1, 1, tiles={"whole": 7, "diag": 8, "edge": 6, "both": 0},
            fetches=(20, 20)))
    for _ in range(2):
        kt.note_tile_classes(8192, 0, 1024, 1024, 128, _stats(
            1, 1, 1, tiles={"whole": 28, "diag": 8, "edge": 0, "both": 0},
            fetches=(35, 35)))
    assert reader.read(ctx) == pytest.approx(
        (8 * 40 + 2 * 70) / (2.0 * (8 * 21 + 2 * 36)))
