"""Kimi-Linear-48B-A3B (moonshotai; model type `kimi_linear`,
https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct): a
decoder-only LM whose token mixers are of two kinds, a per-layer choice
`linear_attn_config` makes with two lists of 1-based layer numbers
(`kda_layers`, `full_attn_layers`; three to one as published), and whose
feed-forward is a dense SwiGLU MLP in the first `first_k_dense_replace`
layers and, after them, a shared expert every token passes through beside
a token-choice mixture of routed ones.

Block i: x += Mixer_i(rms(x)); x += F_i(rms(x)); a final rms; an untied
head.  No bias on any projection, no position encoding anywhere.

  KDA   Kimi Delta Attention, under the name scope `kda`:
        proj  q, k, v = h W_q, h W_k, h W_v (heads x head_dim each); the
              decay's and the output gate's low-rank projections
              (h W_fa) W_fb + dt_bias and (h W_ga) W_gb; beta's h W_b
        conv  a depthwise causal convolution of `short_conv_kernel_size`
              taps and SiLU on each of q, k, v (`causal_conv`), then an L2
              norm over every q and k head
        gate  g = -exp(A_log[head]) softplus(.), the log-decay of every
              key CHANNEL, and beta = sigmoid(.), both float32
        core  one `kda_attention` op (ops/kda_ops.py): per head a
              head_dim x head_dim state, decayed channel by channel,
              corrected by the delta rule, read by q head_dim^-0.5
        out   an RMSNorm over every head's output times sigmoid of the
              gate (the published FusedRMSNormGated), then W_o
  MLA   `transformer.latent_attention(rotary=False)` (`mla_use_nope`):
        kanana-2's latent attention without any rotary embedding.
  F_i   i < first_k_dense_replace: one SwiGLU MLP of `intermediate_size`.
        else Shared(h) + Routed(h).  Routed: one `moe_ffn` op, s =
        sigmoid(h W_r) in f32, the top-k of s + e_score_correction_bias,
        weights the unbiased s renormalised over the chosen (+ 1e-20;
        `moe_renormalize`) and multiplied by `routed_scaling_factor`;
        `num_local_experts` / `expert_offset` build one chip's share of
        every expert layer (the router keeps its width).  Shared:
        `num_shared_experts` x `moe_intermediate_size` wide, under
        `shared_expert`, computed alike on every chip.

The train-program plumbing is `decoder.lm_train_program`;
`kimi_linear_reference.py` is the plain float32 statement of the same
equations, with KDA as the token-by-token recurrence.
"""

from .. import framework, layers
from ..param_attr import ParamAttr
from . import transformer as tfm
from .decoder import (A_RANGE, DT_RANGE, EXPERT_BIAS_STD, L2_EPS,
                      InverseSoftplusOfLogUniform, LogUniform, beside_shared,
                      fc, lm_train_program, norm_or_weight, routed_experts,
                      swiglu_mlp, weight, xent_cost)

__all__ = ["KimiLinearConfig", "kimi_linear_lm", "kimi_linear_lm_program"]

# e_score_correction_bias is a buffer in the published modeling code, zero
# at initialisation; the rule that moves it in training is the trainer's:
# seeded and balanced as `decoder.EXPERT_BIAS_STD` says.
# what the published gate adds to the chosen scores' sum before it divides
_NORM_TOPK_EPS = 1e-20
# what a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "kimi_linear_eval_rows"


class KimiLinearConfig:
    """Kimi-Linear-48B-A3B-Instruct under the keys of its published
    config.json; subclass to shrink for tests or to cut to a chip's
    share."""

    vocab_size = 163840
    hidden_size = 2304
    intermediate_size = 9216       # width of the dense layer's MLP
    moe_intermediate_size = 1024   # width of one expert
    num_hidden_layers = 27
    first_k_dense_replace = 1
    moe_layer_freq = 1
    linear_attn_config = {
        "kda_layers": [i for i in range(1, 27) if i % 4],
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27],
        "num_heads": 32, "head_dim": 128, "short_conv_kernel_size": 4,
    }
    num_attention_heads = 32       # the MLA layers'
    num_key_value_heads = 32
    kv_lora_rank = 512
    q_lora_rank = None
    qk_nope_head_dim = 128
    qk_rope_head_dim = 64
    v_head_dim = 128
    mla_use_nope = True
    num_experts = 256              # the router's width
    num_experts_per_token = 8
    num_shared_experts = 1
    moe_router_activation_func = "sigmoid"
    moe_renormalize = True
    routed_scaling_factor = 2.446
    num_expert_group = 1
    topk_group = 1
    rms_norm_eps = 1e-5
    rope_theta = 10000.0           # read only where mla_use_nope is false
    rope_scaling = None
    tie_word_embeddings = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def mixer_of(hp, i):
    """"kda" or "mla" for layer i (0-based; the published lists count from
    1); a layer in neither list, or in both, is refused."""
    la = hp.linear_attn_config
    kinds = [kind for kind, key in (("kda", "kda_layers"),
                                    ("mla", "full_attn_layers"))
             if i + 1 in la[key]]
    if len(kinds) != 1:
        raise ValueError(
            "layer %d is in %s of linear_attn_config's kda_layers %r and "
            "full_attn_layers %r" % (i + 1, "both" if kinds else "neither",
                                     la["kda_layers"], la["full_attn_layers"]))
    return kinds[0]


def _check(hp):
    """What the builder would have to guess, it refuses."""
    for i in range(hp.num_hidden_layers):
        mixer_of(hp, i)
    if hp.num_expert_group != 1 or hp.topk_group != 1:
        raise NotImplementedError(
            "num_expert_group %r / topk_group %r: the router here chooses "
            "among all experts at once" % (hp.num_expert_group,
                                           hp.topk_group))
    if hp.moe_router_activation_func != "sigmoid":
        raise NotImplementedError(
            "moe_router_activation_func %r: the router here is sigmoid "
            "scores with a selection bias"
            % (hp.moe_router_activation_func,))
    if hp.q_lora_rank is not None:
        raise NotImplementedError(
            "q_lora_rank %r: latent_attention projects the query straight "
            "from the hidden state" % (hp.q_lora_rank,))
    if hp.rope_scaling is not None:
        raise NotImplementedError(
            "rope_scaling %r: Kimi-Linear publishes none and this builder reads "
            "none (rotary_embed's scaled frequencies are YaRN's)"
            % (hp.rope_scaling,))
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


def _kda(h, hp):
    """h [B, T, d] -> [B, T, d]: one Kimi Delta Attention mixer."""
    la = hp.linear_attn_config
    n, dh = int(la["num_heads"]), int(la["head_dim"])
    taps, width = int(la["short_conv_kernel_size"]), n * dh
    b, t = h.shape[0], h.shape[1]

    def heads(y):  # [B, T, n dh] -> [B, T, n, dh]
        return layers.reshape(y, [b, t, n, dh])

    def lead(y):  # [B, T, n, dh] -> [B, n, T, dh]
        return layers.transpose(y, [0, 2, 1, 3])

    with framework.name_scope("kda"):
        with framework.name_scope("proj"):
            q, k, v = (fc(h, width, "kda_%s.w" % name) for name in "qkv")
            decay = fc(fc(h, dh, "kda_f_a.w"), width, "kda_f_b.w",
                       bias_attr=ParamAttr(
                           name=framework.unique_name.generate("kda_dt.b"),
                           initializer=InverseSoftplusOfLogUniform(*DT_RANGE)))
            gate = fc(fc(h, dh, "kda_g_a.w"), width, "kda_g_b.w")
            beta = fc(h, n, "kda_b.w")
        with framework.name_scope("conv"):
            q, k, v = (heads(layers.causal_conv(
                y, taps, act="silu",
                param_attr=weight("kda_%s_conv.w" % name)))
                for y, name in ((q, "q"), (k, "k"), (v, "v")))
            q, k = (layers.l2_normalize(y, axis=-1, epsilon=L2_EPS)
                    for y in (q, k))
            q, k, v = lead(q), lead(k), lead(v)
        with framework.name_scope("gate"):
            a_log = layers.create_parameter(
                [n, 1], "float32",
                attr=ParamAttr(
                    name=framework.unique_name.generate("kda_A_log.w"),
                    initializer=LogUniform(*A_RANGE)))
            g = lead(layers.elementwise_mul(
                heads(layers.softplus(decay)),
                layers.scale(layers.exp(a_log), scale=-1.0), axis=2))
            beta = layers.transpose(layers.sigmoid(beta), [0, 2, 1])
        with framework.name_scope("core"):
            o = layers.kda_attention(q, k, v, g, beta)
        with framework.name_scope("out"):
            o = layers.rms_norm(layers.transpose(o, [0, 2, 1, 3]),
                                hp.rms_norm_eps,
                                param_attr=tfm.named("kda_o_norm.w"))
            o = layers.elementwise_mul(o, layers.sigmoid(heads(gate)))
            return fc(layers.reshape(o, [b, t, width]), hp.hidden_size,
                      "kda_o.w")


def _mla(h, hp):
    return tfm.latent_attention(
        h, hp.num_attention_heads, hp.kv_lora_rank, hp.qk_nope_head_dim,
        hp.qk_rope_head_dim, hp.v_head_dim, norm_eps=hp.rms_norm_eps,
        rotary_base=float(hp.rope_theta), rotary_interleaved=True,
        param_attr=norm_or_weight, rotary=not hp.mla_use_nope)


def _experts(h, hp, is_test):
    routed, _ = routed_experts(
        h, is_test, hp.num_experts, hp.moe_intermediate_size,
        hp.num_experts_per_token, norm_topk_prob=hp.moe_renormalize,
        router="sigmoid",
        expert_bias_attr=weight("moe_e_score_correction_bias.b",
                                EXPERT_BIAS_STD),
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.routed_scaling_factor,
        norm_topk_eps=_NORM_TOPK_EPS)

    def shared(h):
        return swiglu_mlp(h, hp.num_shared_experts * hp.moe_intermediate_size,
                          hp.hidden_size, "shared_ffn")

    return beside_shared(h, routed, shared if hp.num_shared_experts else None)


def _block(x, hp, i, is_test):
    h = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("attn_norm.w"))
    a = _kda(h, hp) if mixer_of(hp, i) == "kda" else _mla(h, hp)
    x = layers.elementwise_add(x, a)
    h = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("ffn_norm.w"))
    m = (swiglu_mlp(h, hp.intermediate_size, hp.hidden_size, "ffn")
         if i < hp.first_k_dense_replace else _experts(h, hp, is_test))
    return layers.elementwise_add(x, m)


def kimi_linear_lm(ids, hp=KimiLinearConfig, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    its own matrix (`tie_word_embeddings` false)."""
    _check(hp)
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    for i in range(hp.num_hidden_layers):
        x = _block(x, hp, i, is_test)
    x = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("final_norm.w"))
    return fc(x, hp.vocab_size, "softmax_out.w")


def kimi_linear_lm_program(hp=KimiLinearConfig, seq_len=8192, lr=5e-6,
                           is_test=False, use_bf16=False, mesh=None,
                           bias_rate=None, bias_max_step=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; a training step ends with the selection biases'
    balancing step, as trinity_lm_program's (`bias_rate` / `bias_max_step`:
    the `expert_bias_update` op's `rate` and `max_step` where given); an
    `is_test` program leaves every token's cost in the scope under
    EVAL_ROWS."""
    return lm_train_program(
        lambda ids, labels: (
            xent_cost(kimi_linear_lm(ids, hp, is_test), labels), None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS, bias_rate=bias_rate, bias_max_step=bias_max_step)
