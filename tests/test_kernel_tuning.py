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
