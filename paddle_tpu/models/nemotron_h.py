"""NVIDIA-Nemotron-3-Nano-30B-A3B (NVIDIA; model type `nemotron_h`,
https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16): a
decoder-only LM whose layers are of three kinds and each ONE of them: a
Mamba-2 mixer, a mixture of experts, or softmax attention, as the
characters of `hybrid_override_pattern` say (`M`, `E`, `*`; `-`, a dense
MLP, is refused by name: no published layer of this model has it).

Layer i: x += Block_i(rms(x)), one norm, one block, one add; a final rms;
an untied head.  Every rms has a plain gain (one at initialisation) and
`layer_norm_epsilon`.  No bias on any projection.

  M   the Mamba-2 mixer, under the name scope `mamba2` (H = `mamba_num_heads`
      heads of P = `mamba_head_dim`, d_inner = H P and NOT `expand` x hidden;
      G = `n_groups`, N = `ssm_state_size`):
      in_proj   [z | xBC | dt] = h W_in, W_in [d, 2 H P + 2 G N + H]
      conv      xBC = silu(conv(xBC) + b): ONE depthwise causal convolution
                of `conv_kernel` taps over the H P + 2 G N channels, with its
                bias (`use_conv_bias`); then x [B, T, H, P], B and C
                [B, T, G, N] are split off and brought heads-leading
      core      dt = softplus(dt + dt_bias[j]) in float32 (`time_step_limit`
                (0, inf): no clamp), A_j = -exp(A_log[j]); one `mamba2_scan`
                op (ops/mamba2_ops.py): S_t = exp(dt_t A_j) S_{t-1} + dt_t
                x_t B_t^T, y_t = S_t C_t + D_j x_t; head j reads group
                j // (H / G)
      norm      y silu(z), THEN a plain-gain RMSNorm over each group of
                H P / G channels, the gain [G, H P / G] (the published
                `MambaRMSNormGated`: gate first, `group_size` = d_inner /
                n_groups)
      out_proj  W_out [H P, d]
  *   the shared `transformer.multi_head_attention` under `attn_full`:
      grouped queries (32 over 2) of `head_dim`, no QK-norm, no gate and NO
      rotary: the published `NemotronHAttention` applies none
      (`rope_theta` / `partial_rotary_factor` are keys the config carries
      and the layer does not read; Nemotron-H, arXiv:2504.03624, section
      2.1: no position embeddings).
  E   Shared(h) + Routed(h).  Routed: one `moe_ffn` op with `expert_act`
      "relu2" (down(relu(up x)^2): `mlp_hidden_act`), s = sigmoid(h W_r) in
      f32 over all `n_routed_experts`, the top-k of s +
      e_score_correction_bias, weights the unbiased s renormalised over
      the chosen (+ 1e-20) and multiplied by `routed_scaling_factor`;
      `num_local_experts` / `expert_offset` build one chip's share of
      every expert layer (the router keeps its width).  Shared:
      `moe_shared_expert_intermediate_size` wide, the same ungated body,
      under `shared_expert`, computed alike on every chip.

Initialisation is the published mixer's own: normal(0, 0.02) weights, the
convolution's filters uniform(-k, k) at k = `conv_kernel`^-0.5 (nn.Conv1d's
default for a depthwise filter, which the published `_init_weights` leaves:
at 0.02 B and C would be SiLUs of ~0.04 and the state a thousandth of D x,
so that no comparison could tell a wrong scan), the
three projections that write to the residual (W_out, Wo, the experts' and
the shared expert's down) scaled by 1 / sqrt(`num_hidden_layers`)
(`rescale_prenorm_residual`), A_log = log of uniform(1, 16) a head, dt_bias
= softplus^-1 of max(exp(uniform(log `time_step_min`, log
`time_step_max`)), `time_step_floor`), D ones, the convolution's bias zero.

The train-program plumbing is `decoder.lm_train_program`;
`nemotron_h_reference.py` is the plain float32 statement of the same
equations, with the scan as the token-by-token recurrence.
"""

import math

from .. import framework, layers
from ..initializer import Constant, Uniform
from ..param_attr import ParamAttr
from . import transformer as tfm
from .decoder import (A_RANGE, EXPERT_BIAS_STD, InverseSoftplusOfLogUniform,
                      LogUniform, beside_shared, fc, lm_train_program,
                      routed_experts, weight, xent_cost)

__all__ = ["NemotronHConfig", "nemotron_h_lm", "nemotron_h_lm_program"]

# e_score_correction_bias is a buffer without gradient in the published
# modeling code, zero at initialisation: seeded and balanced as
# `decoder.EXPERT_BIAS_STD` says.
# what the published router adds to the chosen scores' sum before it divides
_ROUTE_NORM_EPS = 1e-20
# what a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "nemotron_h_eval_rows"
KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


class NemotronHConfig:
    """Nemotron-3-Nano-30B-A3B under the keys of its published
    config.json; subclass to shrink for tests or to cut to a chip's
    share."""

    vocab_size = 131072
    hidden_size = 2688
    num_hidden_layers = 52
    hybrid_override_pattern = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads = 64
    mamba_head_dim = 64
    n_groups = 8
    ssm_state_size = 128
    conv_kernel = 4
    chunk_size = 128
    use_conv_bias = True
    mamba_proj_bias = False
    mamba_hidden_act = "silu"
    time_step_min = 0.001
    time_step_max = 0.1
    time_step_floor = 1e-4
    num_attention_heads = 32
    num_key_value_heads = 2
    head_dim = 128
    attention_bias = False
    n_routed_experts = 128         # the router's width
    num_experts_per_tok = 6
    n_shared_experts = 1
    moe_intermediate_size = 1856   # width of one routed expert
    moe_shared_expert_intermediate_size = 3712
    n_group = 1
    topk_group = 1
    norm_topk_prob = True
    routed_scaling_factor = 2.5
    mlp_hidden_act = "relu2"
    mlp_bias = False
    layer_norm_epsilon = 1e-5
    rescale_prenorm_residual = True
    tie_word_embeddings = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def kinds_of(hp):
    """("mamba2" | "experts" | "attention") for every layer, read off
    `hybrid_override_pattern` character by character."""
    pattern = str(hp.hybrid_override_pattern)
    if len(pattern) != int(hp.num_hidden_layers):
        raise ValueError(
            "hybrid_override_pattern names %d layers, num_hidden_layers is "
            "%d" % (len(pattern), hp.num_hidden_layers))
    for i, ch in enumerate(pattern):
        if ch == "-":
            raise NotImplementedError(
                "hybrid_override_pattern[%d] is '-', a dense MLP layer: no "
                "published layer of this model has one and none is built"
                % i)
        if ch not in KINDS:
            raise ValueError(
                "hybrid_override_pattern[%d] is %r: neither M (Mamba-2), E "
                "(experts) nor * (attention)" % (i, ch))
    return [KINDS[ch] for ch in pattern]


def _check(hp):
    """What the builder would have to guess, it refuses."""
    if hp.n_group != 1 or hp.topk_group != 1:
        raise NotImplementedError(
            "n_group %r / topk_group %r: the router here chooses among all "
            "experts at once" % (hp.n_group, hp.topk_group))
    if hp.mlp_hidden_act != "relu2" or hp.mamba_hidden_act != "silu":
        raise NotImplementedError(
            "mlp_hidden_act %r / mamba_hidden_act %r: the experts here are "
            "relu2, the mixer's convolution and gate SiLU"
            % (hp.mlp_hidden_act, hp.mamba_hidden_act))
    if hp.mamba_proj_bias or hp.attention_bias or hp.mlp_bias:
        raise NotImplementedError("the published projections have no bias")
    if hp.mamba_num_heads % hp.n_groups:
        raise ValueError("n_groups %d does not divide mamba_num_heads %d"
                         % (hp.n_groups, hp.mamba_num_heads))
    if int(hp.chunk_size) != 128:
        raise NotImplementedError(
            "chunk_size %r: mamba2_scan runs at the published 128"
            % (hp.chunk_size,))
    if hp.tie_word_embeddings:
        raise NotImplementedError("the published head is untied")


def _norm(x, hp, base):
    return layers.rms_norm(x, hp.layer_norm_epsilon,
                           param_attr=tfm.named(base))


def _out_std(hp):
    """Of a projection that writes to the residual."""
    return 0.02 / (math.sqrt(hp.num_hidden_layers)
                   if hp.rescale_prenorm_residual else 1.0)


def _number_a_head(base, heads, initializer):
    return layers.create_parameter(
        [heads], "float32",
        attr=ParamAttr(name=framework.unique_name.generate(base),
                       initializer=initializer))


def _mamba2(h, hp):
    """h [B, T, d] -> [B, T, d]: one Mamba-2 mixer."""
    heads, p = int(hp.mamba_num_heads), int(hp.mamba_head_dim)
    g, n = int(hp.n_groups), int(hp.ssm_state_size)
    inner, b, t = heads * p, h.shape[0], h.shape[1]

    def lead(y, count, width):  # [B, T, count width] -> [B, count, T, width]
        return layers.transpose(layers.reshape(y, [b, t, count, width]),
                                [0, 2, 1, 3])

    with framework.name_scope("mamba2"):
        with framework.name_scope("in_proj"):
            z, xbc, dt = layers.split(
                fc(h, 2 * inner + 2 * g * n + heads, "mamba_in.w"),
                [inner, inner + 2 * g * n, heads], dim=-1)
        with framework.name_scope("conv"):
            reach = float(hp.conv_kernel) ** -0.5
            xbc = layers.causal_conv(
                xbc, int(hp.conv_kernel), act="silu",
                param_attr=ParamAttr(
                    name=framework.unique_name.generate("mamba_conv.w"),
                    initializer=Uniform(-reach, reach)),
                bias_attr=(ParamAttr(
                    name=framework.unique_name.generate("mamba_conv.b"),
                    initializer=Constant(0.0)) if hp.use_conv_bias else None))
            x, bm, cm = layers.split(xbc, [inner, g * n, g * n], dim=-1)
            x, bm, cm = lead(x, heads, p), lead(bm, g, n), lead(cm, g, n)
        with framework.name_scope("core"):
            dt_bias = _number_a_head(
                "mamba_dt.b", heads, InverseSoftplusOfLogUniform(
                    hp.time_step_min, hp.time_step_max, hp.time_step_floor))
            a_log = _number_a_head("mamba_A_log.w", heads,
                                   LogUniform(*A_RANGE))
            skip = _number_a_head("mamba_D.w", heads, Constant(1.0))
            # in the op's layout, [B, heads, T]; dt_bias joins the
            # projection after a cast to float32 that says so (as the
            # projection's own bias the AMP pass would round the sum to
            # bfloat16: qwen3_next._gdn says what that costs)
            dt = layers.softplus(layers.elementwise_add(
                layers.cast(layers.transpose(dt, [0, 2, 1]), "float32"),
                dt_bias, axis=1))
            y = layers.mamba2_scan(
                x, dt, layers.scale(layers.exp(a_log), scale=-1.0), bm, cm,
                skip)
        with framework.name_scope("norm"):
            y = layers.elementwise_mul(
                layers.reshape(layers.transpose(y, [0, 2, 1, 3]),
                               [b, t, inner]), layers.swish(z))
            y = layers.rms_norm(
                layers.reshape(y, [b, t, g, inner // g]),
                hp.layer_norm_epsilon, param_attr=tfm.named("mamba_norm.w"),
                gain_axes=2)
        with framework.name_scope("out_proj"):
            return fc(layers.reshape(y, [b, t, inner]), hp.hidden_size,
                      "mamba_out.w", _out_std(hp))


def _attention(h, hp, is_test):
    def attr(base):
        return weight(base, _out_std(hp) if base == "mha_o.w" else 0.02)

    with framework.name_scope("attn_full"):
        return tfm.multi_head_attention(
            h, h, h, None, hp.hidden_size, hp.num_attention_heads,
            is_test=is_test, fused=True, causal=True,
            n_kv_head=hp.num_key_value_heads, rotary=False,
            param_attr=attr, head_dim=hp.head_dim, scopes=True)


def _experts(h, hp, is_test):
    routed, _ = routed_experts(
        h, is_test, hp.n_routed_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, gate_up="moe_up.w", down_std=_out_std(hp),
        norm_topk_prob=hp.norm_topk_prob, router="sigmoid",
        expert_bias_attr=weight("moe_expert_bias.b", EXPERT_BIAS_STD),
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.routed_scaling_factor,
        norm_topk_eps=_ROUTE_NORM_EPS, expert_act="relu2")

    def shared(h):
        up = fc(h, hp.n_shared_experts
                * hp.moe_shared_expert_intermediate_size, "shared_ffn_up.w",
                act="relu")
        return fc(layers.square(up), hp.hidden_size, "shared_ffn_out.w",
                  _out_std(hp))

    return beside_shared(h, routed, shared if hp.n_shared_experts else None)


def _block(x, hp, kind, is_test):
    h = _norm(x, hp, "pre_norm.w")
    y = (_mamba2(h, hp) if kind == "mamba2"
         else _attention(h, hp, is_test) if kind == "attention"
         else _experts(h, hp, is_test))
    return layers.elementwise_add(x, y)


def nemotron_h_lm(ids, hp=NemotronHConfig, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    its own matrix (`tie_word_embeddings` false)."""
    _check(hp)
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    for kind in kinds_of(hp):
        x = _block(x, hp, kind, is_test)
    return fc(_norm(x, hp, "final_norm.w"), hp.vocab_size, "softmax_out.w")


def nemotron_h_lm_program(hp=NemotronHConfig, seq_len=8192, lr=5e-6,
                          is_test=False, use_bf16=False, mesh=None,
                          bias_rate=0.03, bias_max_step=0.03):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; a training step ends with one `expert_bias_update` an
    expert layer (`bias_rate` / `bias_max_step`: the op's `rate` and
    `max_step`, a fine-tuning schedule's as trinity's cell runs it); an
    `is_test` program leaves every token's cost in the scope under
    EVAL_ROWS."""
    return lm_train_program(
        lambda ids, labels: (
            xent_cost(nemotron_h_lm(ids, hp, is_test), labels), None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS, bias_rate=bias_rate, bias_max_step=bias_max_step)
