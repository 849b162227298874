"""head_time_share: a data file over readers/scope_time_share.py, the share
of busy time the vocabulary head's ops own (`fused_linear_xent` and its
`_grad`, whatever the role).  The recorded trace under data/ holds no
head, so the share there reads 0."""

import os
import re

import pytest

from conftest import BENCH_DIR, CELLS, RUN, SPEC

from test_program_profile import _read, pp, prof  # noqa: F401 (fixtures)

HOW = RUN.load_json(BENCH_DIR, "layer_metrics", "head_time_share.json")


@pytest.mark.parametrize("scope, selected", [
    ("backward/fused_linear_xent_grad/7/tile_bwd", True),
    ("backward/fused_linear_xent_grad/1126", True),
    ("forward/fused_linear_xent/3", True),
    ("forward/fc/1", False),
    ("backward/fc_grad/7/forward/fused_linear_xent/3", False),
    ("", False),
])
def test_match_selects_the_heads_scopes_only(scope, selected):
    assert HOW["reader"] == "scope_time_share"
    assert bool(re.compile(HOW["args"]["match"]).match(scope)) == selected


def test_a_trace_without_a_head_reads_zero(prof):  # noqa: F811
    ctx = {"program_profile": prof, "load_module": RUN.load_module}
    assert _read("head_time_share", ctx) == 0.0


def test_a_head_in_the_profile_is_counted_in_both_directions(prof):  # noqa: F811
    scopes = dict(prof["scope_ms"])
    scopes["forward/fused_linear_xent/3"] = 0.010
    scopes["backward/fused_linear_xent_grad/7"] = 0.030
    ctx = {"program_profile": dict(prof, scope_ms=scopes),
           "load_module": RUN.load_module}
    assert _read("head_time_share", ctx) == pytest.approx(
        100.0 * 0.040 / prof["busy_ms"])


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_reports_it_and_a_program_without_names_leaves_it_out(
        cell):
    entry = RUN.find(RUN.cell_metrics(SPEC["per_layer"], cell),
                     "head_time_share", "metric")
    assert entry == {
        "name": "head_time_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "Op lowerings + kernels",
        "moves": "train_mfu"}
    assert SPEC["per_layer"][-1] == entry
    assert os.path.isfile(os.path.join(
        BENCH_DIR, "layer_metrics", "head_time_share.json"))
    ctx = {"exe": object(), "main": object(), "log": [].append,
           "load_module": RUN.load_module}
    assert _read("head_time_share", ctx) is None
