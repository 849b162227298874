"""Per-(kernel, shape-bucket) tuning cache (ops/kernel_tuning.py): seed/
hit/search semantics, JSON persistence + reload, pinned consult-only
mode, shape bucketing, corrupt-file tolerance, and the attribution
counters."""

import json
import os

import numpy as np
import pytest

from paddle_tpu import flags
from paddle_tpu.ops import kernel_tuning as kt


@pytest.fixture(autouse=True)
def _fresh_cache():
    """Every test starts with an empty in-memory cache and default
    flags; restores both afterwards."""
    kt.clear_cache(forget_path=True)
    kt.reset_attribution()
    old = {k: flags.get_flag(k) for k in ("kernel_tune_cache",
                                          "kernel_autotune")}
    yield
    flags.set_flags(old)
    kt.clear_cache(forget_path=True)
    kt.reset_attribution()


def test_miss_seeds_default_then_hits():
    flags.set_flags({"kernel_tune_cache": ""})
    default = {"block_rows": 256}
    got = kt.tuned_params("ln", [(64, 128)], "float32", [], default)
    assert got == default
    got2 = kt.tuned_params("ln", [(64, 128)], "float32", [],
                           {"block_rows": 999})
    # second consult is a HIT on the seeded entry, not the new default
    assert got2 == default
    stats = kt.attribution()["tuning"]
    assert stats["misses"] == 1 and stats["hits"] == 1
    assert stats["searches"] == 0  # interpret mode never searches


def test_injected_measure_searches_picks_best_and_persists(tmp_path):
    path = str(tmp_path / "tune.json")
    flags.set_flags({"kernel_tune_cache": path, "kernel_autotune": True})
    costs = {8: 3.0, 16: 1.0, 32: 2.0}
    cands = [{"block_rows": b} for b in (8, 16, 32)]
    got = kt.tuned_params(
        "ln", [(64, 128)], "float32", cands, {"block_rows": 8},
        measure=lambda p: costs[p["block_rows"]])
    assert got == {"block_rows": 16}
    stats = kt.attribution()["tuning"]
    assert stats["searches"] == 1 and stats["search_ms"] >= 0.0

    # persisted: a fresh process (simulated by dropping the in-memory
    # cache) reloads the searched decision from disk
    assert os.path.exists(path)
    raw = json.load(open(path))
    assert any(v.get("searched") for v in raw["entries"].values())
    kt.clear_cache(forget_path=True)
    got2 = kt.tuned_params(
        "ln", [(64, 128)], "float32", cands, {"block_rows": 8},
        measure=lambda p: (_ for _ in ()).throw(AssertionError(
            "a reloaded entry must not re-search")))
    assert got2 == {"block_rows": 16}


def test_autotune_off_is_consult_only(tmp_path):
    """The CI regime: a pinned cache + FLAGS_kernel_autotune=0 — misses
    seed the default and NEVER search, and the pinned file stays
    untouched (only searched decisions persist)."""
    path = str(tmp_path / "pinned.json")
    json.dump({"version": 1, "entries": {}}, open(path, "w"))
    before = open(path).read()
    flags.set_flags({"kernel_tune_cache": path, "kernel_autotune": False})
    got = kt.tuned_params(
        "flash", [(4, 64, 16)], "float32",
        [{"block_q": 128}], {"block_q": 64},
        measure=lambda p: (_ for _ in ()).throw(AssertionError(
            "autotune off must not measure")))
    assert got == {"block_q": 64}
    assert open(path).read() == before


def test_candidate_errors_are_skipped():
    """A candidate whose measurement raises (illegal block shapes
    surface as compile errors) is skipped and counted, not fatal —
    unless EVERY candidate raises: then the search raises with the last
    message instead of seeding the default silently."""
    flags.set_flags({"kernel_tune_cache": "", "kernel_autotune": True})

    def measure(p):
        if p["b"] == 1:
            raise RuntimeError("mosaic says no")
        return float(p["b"])

    got = kt.tuned_params("k", [(8, 8)], "float32",
                          [{"b": 1}, {"b": 3}, {"b": 2}], {"b": 9},
                          measure=measure)
    assert got == {"b": 2}
    stats = kt.attribution()["tuning"]
    assert stats["failed_candidates"] == 1
    assert "mosaic says no" in stats["last_failure"]

    with pytest.raises(RuntimeError, match="all 1 candidates.*mosaic says no"):
        kt.tuned_params("k", [(16, 8)], "float32", [{"b": 1}], {"b": 9},
                        measure=measure)
    # the refusal is not cached as a seeded default: asking again raises
    with pytest.raises(RuntimeError, match="mosaic says no"):
        kt.tuned_params("k", [(16, 8)], "float32", [{"b": 1}], {"b": 9},
                        measure=measure)


def test_shape_bucket_rounds_leading_dims_only():
    # leading (row/batch) dims bucket to the next pow2; last dim exact
    assert kt.shape_bucket([(100, 768)]) == "128x768"
    assert kt.shape_bucket([(128, 768)]) == "128x768"
    assert kt.shape_bucket([(3, 5, 96)]) == "4x8x96"
    assert kt.shape_bucket([(7,)]) == "7"
    # multiple operands join deterministically
    assert kt.shape_bucket([(100, 64), (64, 50)]) == "128x64,64x50"
    # same bucket -> same key -> one search serves the whole bucket
    flags.set_flags({"kernel_tune_cache": ""})
    kt.tuned_params("mm", [(100, 64)], "float32", [], {"bm": 1})
    kt.tuned_params("mm", [(128, 64)], "float32", [], {"bm": 2})
    stats = kt.attribution()["tuning"]
    assert stats["misses"] == 1 and stats["hits"] == 1


def test_corrupt_cache_file_starts_empty(tmp_path, capsys):
    path = str(tmp_path / "broken.json")
    open(path, "w").write("{not json")
    flags.set_flags({"kernel_tune_cache": path})
    got = kt.tuned_params("ln", [(8, 8)], "float32", [], {"b": 5})
    assert got == {"b": 5}
    assert "unreadable" in capsys.readouterr().err


def test_attribution_counters_and_reset():
    kt.note_kernel("attention")
    kt.note_kernel("attention")
    kt.note_kernel("xent")
    att = kt.attribution()
    assert att["pallas_hits"] == {"attention": 2, "xent": 1}
    kt.reset_attribution()
    att = kt.attribution()
    assert att["pallas_hits"] == {} and att["tuning"]["hits"] == 0


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


def test_device_kind_isolates_interpret_entries():
    """Interpret-mode (CPU) cache keys carry their own device universe,
    so a CI cache can never leak block sizes onto a real chip."""
    assert kt._device_kind().startswith("interpret-")


def test_measure_candidate_builds_and_times():
    """The real-device measurement helper runs a jitted candidate over
    synthetic operands and returns seconds."""
    import jax.numpy as jnp

    bench = kt.measure_candidate(
        lambda p: (lambda x: x * p["s"]), [((8, 8), "float32")],
        warmup=1, iters=3)
    t = bench({"s": 2.0})
    assert t >= 0.0


def test_search_candidate_traces_do_not_tick_hit_counters():
    """Regression (review finding): candidate timing re-traces kernel
    bodies; those traces must not inflate the per-family pallas-hit
    attribution."""
    flags.set_flags({"kernel_tune_cache": "", "kernel_autotune": True})

    def measure(p):
        kt.note_kernel("attention")  # what a candidate trace would do
        return float(p["b"])

    kt.tuned_params("flash", [(8, 8)], "float32",
                    [{"b": 1}, {"b": 2}, {"b": 3}], {"b": 1},
                    measure=measure)
    assert kt.attribution()["pallas_hits"].get("attention", 0) == 0
    # outside a search the counter ticks normally again
    kt.note_kernel("attention")
    assert kt.attribution()["pallas_hits"]["attention"] == 1


def test_seeded_entries_never_persist_alongside_searched(tmp_path):
    """Regression (review finding): a later search's save must not drag
    in-memory SEEDED entries onto disk — a seeded default frozen into
    the persisted cache would pin its kernel to the unmeasured
    heuristic forever (the next process hits instead of re-searching)."""
    path = str(tmp_path / "tune.json")
    flags.set_flags({"kernel_tune_cache": path, "kernel_autotune": True})
    # a consult with nothing to search -> seeded default entry
    kt.tuned_params("broken", [(8, 8)], "float32", [], {"b": 7})
    # a successful search elsewhere triggers the save
    kt.tuned_params("fine", [(8, 8)], "float32", [{"b": 2}], {"b": 9},
                    measure=lambda p: 1.0)
    raw = json.load(open(path))
    assert all(v.get("searched") for v in raw["entries"].values())
    assert not any("broken" in k for k in raw["entries"])
    # a fresh process searches the seeded kernel instead of hitting
    kt.clear_cache(forget_path=True)
    got = kt.tuned_params("broken", [(8, 8)], "float32", [{"b": 1}],
                          {"b": 7}, measure=lambda p: 1.0)
    assert got == {"b": 1}
