"""The seam PR 59 drew: `models/decoder.py` is the one home of what the
decoder-only builders share, no such builder imports another model's file,
and `lm_train_program` ends a Program as the builders used to themselves
(an evaluation's rows under `is_test`, the selection biases' balancing step
after the optimizer)."""

import ast
import pathlib

import pytest

from paddle_tpu import layers
from paddle_tpu.models import decoder

MODELS = pathlib.Path(decoder.__file__).parent
BUILDERS = {"gpt2", "olmoe", "lfm2", "ouro", "kanana2", "trinity",
            "kimi_linear", "qwen3_next", "nemotron_h", "joyai_flash"}
SEQ, D, VOCAB = 8, 16, 32


def _siblings(path):
    """The modules of `paddle_tpu/models/` a file imports, wherever in the
    file the import stands."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if not isinstance(node, ast.ImportFrom) or node.level != 1:
            continue
        found |= ({node.module.split(".")[0]} if node.module
                  else {alias.name for alias in node.names})
    return found


def test_a_decoder_only_builder_imports_no_other_models_file():
    shared = {p.stem for p in MODELS.glob("*.py")
              if "decoder" in _siblings(p)}
    assert shared >= BUILDERS, BUILDERS - shared
    for name in sorted(shared | {"decoder"}):
        extra = _siblings(MODELS / (name + ".py")) - {
            "decoder", "transformer", "decode_cache"}
        assert not extra, (name, extra)


def _trunk(layer_count, bias, is_test=False):
    """A trunk of `layer_count` expert layers and an untied head."""
    def build(ids, labels):
        x = layers.embedding(ids, size=[VOCAB, D],
                             param_attr=decoder.weight("emb.w"))
        for _ in range(layer_count):
            routed, _ = decoder.routed_experts(
                x, is_test, 4, D, 2, router="sigmoid",
                expert_bias_attr=(decoder.weight("moe_expert_bias.b")
                                  if bias else None))
            x = layers.elementwise_add(x, routed)
        return decoder.xent_cost(
            decoder.fc(x, VOCAB, "softmax_out.w"), labels), None
    return build


def _program(trunk, is_test=False, **kw):
    return decoder.lm_train_program(trunk, SEQ, 1e-3, is_test, False, None,
                                    "gpt2", **kw)[0]


def _balancing_ops(main):
    return [op for op in main.global_block().ops
            if op.type == "expert_bias_update"]


@pytest.mark.parametrize("layer_count,bias,is_test,kw,attrs", [
    (2, False, False, {}, None),
    (0, False, False, {"bias_rate": 0.03}, None),
    (2, True, True, {"bias_rate": 0.03, "bias_max_step": 0.03}, None),
    (2, True, False, {}, {}),
    (3, True, False, {"bias_rate": 0.03, "bias_max_step": 0.25},
     {"rate": 0.03, "max_step": 0.25}),
], ids=["no_bias", "no_expert_layer", "is_test", "op_defaults", "scheduled"])
def test_a_training_program_balances_the_biases_it_has(
        layer_count, bias, is_test, kw, attrs):
    """One `expert_bias_update` per `moe_ffn` with an `ExpertBias` input,
    last in the Program and under the optimize role, with `rate` /
    `max_step` where given; none without such an input, none under
    `is_test`."""
    main = _program(_trunk(layer_count, bias, is_test), is_test, **kw)
    ops = _balancing_ops(main)
    if attrs is None:
        assert not ops
        return
    block = main.global_block()
    assert ops == block.ops[-layer_count:]
    biases = [op.inputs["ExpertBias"] for op in block.ops
              if op.type == "moe_ffn"]
    assert [op.inputs["ExpertBias"] for op in ops] == biases
    for op in ops:
        assert op.outputs["ExpertBiasOut"] == op.inputs["ExpertBias"]
        assert op.attrs["op_role"] == "optimize"
        assert {k: op.attrs[k] for k in ("rate", "max_step")
                if k in op.attrs} == attrs


@pytest.mark.parametrize("is_test,name", [
    (True, None), (False, "rows_of_a_test"), (True, "rows_of_a_test")],
    ids=["unnamed", "named_but_training", "named"])
def test_an_eval_program_leaves_the_rows_it_is_asked_for(is_test, name):
    """`eval_rows=None` leaves no persistable behind, a name leaves the
    trunk's cost as [B, T] float32, and only under `is_test`."""
    def kept(**kw):
        block = _program(_trunk(0, False), is_test, **kw).global_block()
        return {n: v for n, v in block.vars.items()
                if v.persistable and not n.startswith(("emb.w",
                                                       "softmax_out.w"))}

    plain, rows = kept(), kept(eval_rows=name)
    if not (is_test and name):
        assert set(rows) == set(plain)
        return
    assert set(rows) - set(plain) == {name}
    assert tuple(rows[name].shape) == (-1, SEQ)
    assert str(rows[name].dtype).endswith("float32")
    assert rows[name].stop_gradient


def test_the_experts_statistic_is_named_in_one_place():
    """Every builder's expert layers go through `routed_experts`: the one
    `layers.moe_ffn` call and the one `_eval` statistic name under
    `models/` (the references apart, which build no Program)."""
    sources = {p.name: p.read_text() for p in MODELS.glob("*.py")
               if not p.stem.endswith("_reference")}
    for needle in ("layers.moe_ffn(", '"moe_tokens_per_expert_eval"'):
        assert [n for n, text in sources.items() if needle in text] == [
            "decoder.py"], needle
        assert sources["decoder.py"].count(needle) == 1
