"""What the decoder-only builders share: the one home of the code that is
the same in every one of them, so that a model file imports this module and
`transformer.py` (the attention builders) and no other model's file.

  attributes    `weight` (a named normal(0, std) matrix), `norm_or_weight`
                (what the attention builders take as `param_attr`), `fc`
                (a projection over the last axis of [B, T, d]).
  feed-forward  `swiglu_mlp`; `routed_experts`, the one `layers.moe_ffn`
                call under `models/`, and `beside_shared`, a shared branch
                added to it.  A model's own `_experts(h, hp, is_test)` maps
                its published config keys onto them; `deepseek_v3_block`
                (with its `_check` and `_experts`) is the whole block of
                the published `deepseek_v3` modeling code, which more than
                one model stacks.
  recurrences   `LogUniform`, `InverseSoftplusOfLogUniform` and the ranges
                the delta-rule and state-space mixers draw their decay from.
  the program   `xent_cost` and `lm_train_program`: feeds, the weighted
                token cost, the fuse passes, AMP, remat, Adam, the mesh
                stamp, an evaluation's rows and the selection biases'
                balancing step.

Nothing here branches on which model calls it: what differs between two
models (norm placement, the mixers, the router's constants, the trunk)
stays in their files.
"""

import math

from .. import framework, layers, unique_name
from ..initializer import Initializer, Normal, Uniform
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr
from . import transformer as tfm

__all__ = [
    "EXPERT_BIAS_STD", "L2_EPS", "A_RANGE", "DT_RANGE", "weight",
    "norm_or_weight", "fc", "swiglu_mlp", "routed_experts", "beside_shared",
    "NORM_TOPK_EPS", "deepseek_v3_check", "deepseek_v3_experts",
    "deepseek_v3_block", "LogUniform", "InverseSoftplusOfLogUniform",
    "xent_cost", "leave_eval_rows", "balance_expert_biases",
    "lm_train_program",
]

# A selection bias (LFM2's expert_bias, the DeepSeek-V3 family's
# e_score_correction_bias) is a buffer in the published modeling codes, zero
# at initialisation, and the rule that moves it in training is the
# trainer's.  This repo's own choice: seeded non-zero, so that selection
# (score + bias) and weights (score alone) differ from the first step, and
# after every training step moved against each expert's share of the load
# (`balance_expert_biases`).
EXPERT_BIAS_STD = 0.1
# the published l2norm's epsilon (inside the square root, per head)
L2_EPS = 1e-6
# the published initialisation of a recurrent mixer's decay: A =
# uniform(1, 16) a head, dt = exp(uniform(log 0.001, log 0.1)), dt_bias =
# softplus^-1(dt)
A_RANGE = (1.0, 16.0)
DT_RANGE = (0.001, 0.1)


def weight(base, std=0.02):
    """A named normal(0, std) parameter: `parallel.partition_rules` shards
    by these names."""
    return ParamAttr(
        name=unique_name.generate(base), initializer=Normal(0.0, std)
    )


def norm_or_weight(base):
    """normal(0, 0.02) for a matrix, ones for a norm's gain."""
    return tfm.named(base) if "norm" in base else weight(base)


def fc(x, size, base, std=0.02, act=None, bias_attr=False):
    """x [B, T, d] -> [B, T, size] through the matrix named `base`."""
    return layers.fc(x, size=size, num_flatten_dims=2, bias_attr=bias_attr,
                     act=act, param_attr=weight(base, std))


def swiglu_mlp(h, width, d, prefix, weight=weight):
    """The three `fc` ops the fuse pass turns into `fused_swiglu`;
    `weight(base)` names their matrices."""
    gate = layers.fc(h, size=width, num_flatten_dims=2, act="swish",
                     bias_attr=False, param_attr=weight(prefix + "_gate.w"))
    up = layers.fc(h, size=width, num_flatten_dims=2, bias_attr=False,
                   param_attr=weight(prefix + "_up.w"))
    return layers.fc(layers.elementwise_mul(gate, up), size=d,
                     num_flatten_dims=2, bias_attr=False,
                     param_attr=weight(prefix + "_out.w"))


def routed_experts(h, is_test, num_experts, width, top_k,
                   gate_up="moe_gate_up.w", down_std=0.02, **moe):
    """One `moe_ffn` op over h -> (routed [B, T, d], aux [2]).  `moe` goes
    through to `layers.moe_ffn`, whose defaults are the only ones: what a
    model does not pass it does not set.  An `is_test` program keeps its
    counts under a name of its own, so that an evaluation which shares a
    scope with the training program leaves the step's alone."""
    routed, aux, _ = layers.moe_ffn(
        h, num_experts, width, top_k, router_attr=weight("moe_router.w"),
        gate_up_attr=weight(gate_up),
        down_attr=weight("moe_down.w", down_std),
        stat_name=("moe_tokens_per_expert_eval" if is_test
                   else "moe_tokens_per_expert"), **moe)
    return routed, aux


def beside_shared(h, routed, shared):
    """`routed` alone where `shared` is None, else `shared(h)`, built under
    the name scope `shared_expert`, added to it."""
    if shared is None:
        return routed
    with framework.name_scope("shared_expert"):
        return layers.elementwise_add(shared(h), routed)


# --------------------------------------------------------------------------
# the block of the published `deepseek_v3` modeling code, under the keys of
# its config.json: what kanana-2 and JoyAI-LLM-Flash stack (Kimi-Linear's
# block mixes two kinds of mixer under other keys and stays in its file)
# --------------------------------------------------------------------------
# what the family adds to the chosen scores' sum before it divides
NORM_TOPK_EPS = 1e-20


def deepseek_v3_check(hp):
    """What the builder would have to guess, it refuses."""
    if hp.n_group != 1 or hp.topk_group != 1:
        raise NotImplementedError(
            "n_group %r / topk_group %r: the router here chooses among all "
            "experts at once (one group, where the group limit is the "
            "identity)" % (hp.n_group, hp.topk_group))
    if hp.scoring_func != "sigmoid" or hp.topk_method != "noaux_tc":
        raise NotImplementedError(
            "scoring_func %r / topk_method %r: the router here is sigmoid "
            "scores with a selection bias (noaux_tc)"
            % (hp.scoring_func, hp.topk_method))
    if hp.rope_scaling is not None:
        raise NotImplementedError(
            "rope_scaling %r: this block reads none (rotary_embed's scaled "
            "frequencies are YaRN's, built from Laguna's `rope_parameters`; "
            "the softmax scale has no mscale)" % (hp.rope_scaling,))
    if hp.moe_layer_freq != 1:
        raise NotImplementedError(
            "moe_layer_freq %r: every layer after the leading dense ones "
            "is an expert layer here" % (hp.moe_layer_freq,))
    if hp.num_key_value_heads != hp.num_attention_heads:
        raise ValueError(
            "num_key_value_heads %d is not num_attention_heads %d: latent "
            "attention expands a key and a value for every head"
            % (hp.num_key_value_heads, hp.num_attention_heads))
    if hp.tie_word_embeddings:
        raise NotImplementedError("the published head is untied")


def deepseek_v3_experts(h, hp, is_test):
    """Shared(h) + Routed(h): one `moe_ffn` with the `noaux_tc` router
    beside the `n_shared_experts` shared experts as ONE SwiGLU MLP."""
    routed, _ = routed_experts(
        h, is_test, hp.n_routed_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=hp.norm_topk_prob,
        router="sigmoid",
        expert_bias_attr=weight("moe_e_score_correction_bias.b",
                                EXPERT_BIAS_STD),
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.routed_scaling_factor,
        norm_topk_eps=NORM_TOPK_EPS)

    def shared(h):
        return swiglu_mlp(h, hp.n_shared_experts * hp.moe_intermediate_size,
                          hp.hidden_size, "shared_ffn")

    return beside_shared(h, routed, shared if hp.n_shared_experts else None)


def deepseek_v3_block(x, hp, i, is_test):
    """x += MLA(rms(x)); x += F_i(rms(x)), F_i the dense SwiGLU MLP in the
    first `first_k_dense_replace` layers and the experts after them; a
    `q_lora_rank` puts a latent under the query."""
    h = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("attn_norm.w"))
    a = tfm.latent_attention(
        h, hp.num_attention_heads, hp.kv_lora_rank, hp.qk_nope_head_dim,
        hp.qk_rope_head_dim, hp.v_head_dim, norm_eps=hp.rms_norm_eps,
        rotary_base=float(hp.rope_theta),
        rotary_interleaved=bool(hp.rope_interleave),
        param_attr=norm_or_weight, q_lora_rank=hp.q_lora_rank)
    x = layers.elementwise_add(x, a)
    h = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("ffn_norm.w"))
    m = (swiglu_mlp(h, hp.intermediate_size, hp.hidden_size, "ffn")
         if i < hp.first_k_dense_replace
         else deepseek_v3_experts(h, hp, is_test))
    return layers.elementwise_add(x, m)


class LogUniform(Initializer):
    """log of a uniform(low, high) draw: A_log."""

    def __init__(self, low, high):
        self.draw = Uniform(low, high)

    def __call__(self, var, block):
        self.draw(var, block)
        return block.append_op("log", inputs={"X": [var]},
                               outputs={"Out": [var]})


class InverseSoftplusOfLogUniform(Initializer):
    """softplus^-1(dt) = log(exp(dt) - 1) of dt = exp(uniform(log low,
    log high)), or of max(dt, floor) where a floor is given (Mamba-2's
    `time_step_floor`): dt_bias."""

    def __init__(self, low, high, floor=None):
        self.draw = Uniform(math.log(low), math.log(high))
        self.floor = floor

    def __call__(self, var, block):
        self.draw(var, block)
        same = {"inputs": {"X": [var]}, "outputs": {"Out": [var]}}
        block.append_op("exp", **same)
        if self.floor is not None:
            block.append_op("clip", attrs={"min": float(self.floor),
                                           "max": 3.4e38}, **same)
        block.append_op("exp", **same)
        block.append_op("scale", attrs={"scale": 1.0, "bias": -1.0}, **same)
        return block.append_op("log", **same)


def xent_cost(logits, labels):
    """[B, T, vocab] logits and [B, T] labels -> the [B, T, 1]
    cross-entropy of every token: what a trunk with one set of logits
    returns as its cost."""
    return layers.softmax_with_cross_entropy(
        logits, layers.unsqueeze(labels, [2])
    )


def leave_eval_rows(cost, name, seq_len):
    """Every token's cost, [B, T, 1], left in the scope as the persistable
    [B, T] float32 `name` (what a forward-only program hands an evaluation
    that pairs rows with a reference's)."""
    rows = LayerHelper(name).create_global_variable(
        name=name, persistable=True, dtype="float32", shape=[-1, seq_len])
    rows.stop_gradient = True
    layers.assign(layers.reshape(cost, [-1, seq_len]), output=rows)


def balance_expert_biases(main, rate=None, max_step=None):
    """After the optimizer, one `expert_bias_update` per mixture layer
    that selects with a bias: the bias follows the step's own counts.
    `rate` and `max_step` become the op's attributes where given (a
    fine-tuning schedule's smaller, bounded step); left out, the op is the
    one every program before had, attribute for attribute.  A program
    without such a layer gains nothing."""
    attrs = {k: float(v) for k, v in (("rate", rate), ("max_step", max_step))
             if v is not None}
    block = main.global_block()
    with main._op_role_guard("optimize"):
        for op in list(block.ops):
            if op.type == "moe_ffn" and op.inputs.get("ExpertBias"):
                bias = op.inputs["ExpertBias"]
                block.append_op(
                    "expert_bias_update",
                    inputs={"ExpertBias": bias,
                            "TokensPerExpert": op.outputs["TokensPerExpert"]},
                    outputs={"ExpertBiasOut": bias}, attrs=attrs)


def lm_train_program(trunk, seq_len, lr, is_test, use_bf16, mesh,
                     partition_family, eval_rows=None, bias_rate=None,
                     bias_max_step=None):
    """The causal-LM program every decoder-only builder returns: (main,
    startup, feeds, [loss, token_count]) with feeds ids / labels [B, T]
    int64 and loss_weight [B, T] float, built under `unique_name.guard()`.
    `trunk(ids, labels)` builds the model and returns ([B, T, 1] cost of
    every token, extra) where extra is a scalar var added to the loss (a
    mixture's router losses) or None; a trunk with one set of logits ends
    in `xent_cost(logits, labels)`, ouro's in its expected loss over the
    exit steps.  Around it: the weighted token mean, the fuse passes, AMP,
    remat, Adam and the mesh stamp.  An `is_test` program leaves the
    trunk's cost in the scope as [B, T] `eval_rows` where that is named; a
    training program ends, after the mesh stamp, with the selection biases'
    balancing step (`bias_rate` / `bias_max_step`: the
    `expert_bias_update` op's `rate` and `max_step` where given)."""
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        ids = layers.data("ids", shape=[seq_len], dtype="int64")
        lbl = layers.data("labels", shape=[seq_len], dtype="int64")
        w = layers.data("loss_weight", shape=[seq_len], dtype="float32")

        cost, extra = trunk(ids, lbl)
        if is_test and eval_rows is not None:
            leave_eval_rows(cost, eval_rows, seq_len)
        cost = layers.elementwise_mul(cost, layers.unsqueeze(w, [2]))
        tokens = layers.reduce_sum(w)
        # epsilon guard: an all-pad batch yields loss 0, never 0/0 NaN
        loss = layers.elementwise_div(
            layers.reduce_sum(cost), layers.clip(tokens, 1e-5, 1e30)
        )
        if extra is not None:
            loss = layers.elementwise_add(loss, extra)

        # logits-free fused cross-entropy (fused_linear_xent lowers to
        # linear_xent_tiled: the [B, T, V] f32 logits exist a vocabulary
        # tile at a time) + the fc / fused_swiglu / fused_residual_ln
        # ops for the FFN/residual-LN chains (one dense lowering each,
        # their epilogues fused by XLA) — both BEFORE minimize so grads
        # differentiate through the fused ops
        from ..transpiler.pass_registry import apply_pass

        apply_pass(main, "linear_xent_fuse_pass")
        apply_pass(main, "matmul_epilogue_fuse_pass")
        if use_bf16:
            apply_pass(main, "bf16_amp_pass")
        # HBM-budgeted remat (FLAGS_hbm_budget_bytes; no-op when unset);
        # the flag is a per-device budget, so a mesh scales it
        from ..transpiler.remat import maybe_remat

        maybe_remat(main, loss, is_test, mesh=mesh)
        if not is_test:
            fluid.optimizer.Adam(learning_rate=lr).minimize(loss)

    if mesh is not None:
        from ..parallel.partition_rules import (annotate_spmd,
                                                train_partition_rules_for)

        annotate_spmd(main, mesh,
                      train_partition_rules_for(partition_family))
    if not is_test:
        balance_expert_biases(main, bias_rate, bias_max_step)
    return main, startup, ["ids", "labels", "loss_weight"], [loss, tokens]
