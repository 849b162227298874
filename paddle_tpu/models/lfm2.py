"""LFM2-MoE (LiquidAI LFM2-8B-A1B; model type `lfm2_moe`,
https://huggingface.co/LiquidAI/LFM2-8B-A1B): a decoder-only LM whose
layers mix tokens either with a gated short convolution or with
grouped-query attention, a per-layer choice the config's `layer_types`
list makes, and whose feed-forward is a dense SwiGLU MLP in the first
`num_dense_layers` layers and a token-choice mixture of SwiGLU experts
after them.

Block i: x += Op_i(rms(x)); x += Ffn_i(rms(x)); a final rms; the head is
the embedding, transposed.  No bias anywhere.

  conv            [B, C, u] = split3(h @ W_in); Op = (C * causal depthwise
                  conv_L(B * u)) @ W_out: two `fc` ops around one
                  `short_conv` op.
  full_attention  the shared `transformer.multi_head_attention` (causal,
                  fused, GQA, RoPE over the whole head, an RMSNorm over
                  head_dim on every q and k head before it).
  experts         one `moe_ffn` op: sigmoid router in f32, the top-k of
                  score + expert_bias, weights the unbiased scores
                  renormalised over the chosen, no auxiliary loss; a
                  training program moves the bias against the load after
                  every step (`expert_bias_update`).
                  `num_local_experts` / `expert_offset` build one chip's
                  share of every expert layer (the router keeps its width).

The train-program plumbing is `decoder.lm_train_program`;
`lfm2_reference.py` is the plain float32 statement of the same equations.
"""

from .. import framework, layers
from . import transformer as tfm
from .decoder import (EXPERT_BIAS_STD, fc, lm_train_program, routed_experts,
                      swiglu_mlp, weight, xent_cost)

__all__ = ["LFM2MoEConfig", "lfm2_lm", "lfm2_lm_program"]

# expert_bias is a buffer in the published modeling code, which has no
# training rule; the family trains it as an adaptive routing bias
# (`decoder.EXPERT_BIAS_STD`, `decoder.balance_expert_biases`).


class LFM2MoEConfig:
    """LFM2-8B-A1B under the keys of its published config.json; subclass
    to shrink for tests or to cut to a chip's share."""

    vocab_size = 65536
    hidden_size = 2048
    intermediate_size = 7168       # width of the dense layers' MLP
    moe_intermediate_size = 1792   # width of one expert
    num_hidden_layers = 24
    layer_types = (["conv", "conv", "full_attention"]
                   + ["conv", "conv", "conv", "full_attention"] * 4
                   + ["conv", "conv", "full_attention", "conv", "conv"])
    num_dense_layers = 2
    num_attention_heads = 32
    num_key_value_heads = 8
    num_experts = 32               # the router's width
    num_experts_per_tok = 4
    norm_topk_prob = True
    use_expert_bias = True
    routed_scaling_factor = 1.0    # published: lowers to no instruction
    norm_eps = 1e-5
    rope_theta = 1000000.0
    conv_L_cache = 3
    max_position_embeddings = 128000
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def _conv_operator(h, hp):
    d = hp.hidden_size
    bcx = fc(h, 3 * d, "conv_in.w")
    # PyTorch's Conv1d default scale for L taps a channel
    y = layers.short_conv(
        bcx, hp.conv_L_cache,
        param_attr=weight("conv_filter.w", std=hp.conv_L_cache ** -0.5))
    return fc(y, d, "conv_out.w")


def _attention_operator(h, hp, is_test):
    return tfm.multi_head_attention(
        h, h, h, None, hp.hidden_size, hp.num_attention_heads,
        is_test=is_test, fused=True, causal=True,
        n_kv_head=hp.num_key_value_heads, rotary=True,
        rotary_base=float(hp.rope_theta), qk_norm="head",
        qk_norm_eps=hp.norm_eps)


def _experts(h, hp, is_test):
    bias = (weight("moe_expert_bias.b", EXPERT_BIAS_STD)
            if hp.use_expert_bias else None)
    routed, _ = routed_experts(
        h, is_test, hp.num_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=hp.norm_topk_prob,
        router="sigmoid", expert_bias_attr=bias,
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.routed_scaling_factor)
    return routed


def _block(x, hp, i, is_test):
    kind = hp.layer_types[i]
    h = layers.rms_norm(x, hp.norm_eps,
                        param_attr=tfm.named("operator_norm.w"))
    if kind == "conv":
        a = _conv_operator(h, hp)
    elif kind == "full_attention":
        a = _attention_operator(h, hp, is_test)
    else:
        raise ValueError("layer_types[%d] is %r: neither conv nor "
                         "full_attention" % (i, kind))
    x = layers.elementwise_add(x, a)
    h = layers.rms_norm(x, hp.norm_eps, param_attr=tfm.named("ffn_norm.w"))
    m = (swiglu_mlp(h, hp.intermediate_size, hp.hidden_size, "ffn")
         if i < hp.num_dense_layers else _experts(h, hp, is_test))
    return layers.elementwise_add(x, m)


def lfm2_lm(ids, hp=LFM2MoEConfig, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    the embedding (config.json has no key for it; the family ties)."""
    if len(hp.layer_types) != hp.num_hidden_layers:
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(hp.layer_types),
                                    hp.num_hidden_layers))
    emb_attr = weight("emb.w")
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=emb_attr)
    for i in range(hp.num_hidden_layers):
        x = _block(x, hp, i, is_test)
    x = layers.rms_norm(x, hp.norm_eps, param_attr=tfm.named("final_norm.w"))
    emb = framework.default_main_program().global_block().var(emb_attr.name)
    return layers.matmul(x, emb, transpose_y=True)


def lfm2_lm_program(hp=LFM2MoEConfig, seq_len=8192, lr=4e-4, is_test=False,
                    use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; a training step ends with the selection biases'
    balancing step where the experts select with one."""
    return lm_train_program(
        lambda ids, labels: (xent_cost(lfm2_lm(ids, hp, is_test), labels),
                             None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family)
