"""`state_relayouts`: the data file through its reader on made-up set-up
ledgers, and the registry entry."""

import types

import pytest

from conftest import BENCH_DIR, RUN, SPEC

METRIC = "state_relayouts"


def _read(ctx, monkeypatch, records):
    import paddle_tpu.profiler as profiler

    if records is None:
        monkeypatch.delattr(profiler, "phases", raising=False)
    else:
        monkeypatch.setattr(profiler, "phases", lambda: records)
    how = RUN.load_json(BENCH_DIR, "layer_metrics", METRIC + ".json")
    return RUN.load_module("readers", how["reader"]).read(
        ctx, **how.get("args", {}))


def _record(program, **args):
    return {"name": "trace_compile", "args": dict(args, program=id(program))}


MAIN, OTHER = types.SimpleNamespace(), types.SimpleNamespace()


@pytest.mark.parametrize("records, value", [
    ([_record(OTHER, state_relayouts=3), _record(MAIN, state_relayouts=12),
      _record(MAIN, state_relayouts=1)], 12),  # the first train record
    ([{"name": "import", "args": {}}, _record(MAIN, state_relayouts=0)], 0),
    ([_record(MAIN, trace_s=1.0)], None),   # a record from before the field
    ([_record(OTHER, state_relayouts=3)], None),  # no train record
    ([], None),
    (None, None),                           # a program without the ledger
])
def test_the_reader_takes_the_train_records_field(monkeypatch, records,
                                                  value):
    assert _read({"main": MAIN}, monkeypatch, records) == value
    assert _read({}, monkeypatch, records) is None


def test_the_registry_entry():
    entry = RUN.find(SPEC["per_layer"], METRIC, "metric")
    assert entry == {"name": METRIC, "unit": "count", "better": "higher",
                     "source": "program_counter", "layer": "Trace",
                     "moves": "train_mfu"}
