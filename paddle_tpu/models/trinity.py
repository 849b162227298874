"""Trinity-Mini (arcee-ai; model type `afmoe`,
https://huggingface.co/arcee-ai/Trinity-Mini): a decoder-only LM whose
attention layers are of two kinds, a per-layer choice the config's
`layer_types` list makes (three `sliding_attention` to one
`full_attention`), and whose feed-forward is a dense SwiGLU MLP in the
first `num_dense_layers` layers and, after them, a shared expert every
token passes through beside a token-choice mixture of routed ones.

Block i, four RMSNorms, two of them on a branch's OUTPUT:

    x += rms(Attn_i(rms(x)));  x += rms(F_i(rms(x)))

a final rms; an untied head; the embedding scaled by sqrt(hidden_size)
(`mup_enabled`).  No bias anywhere.

  Attn   the shared `transformer.multi_head_attention`: `head_dim` 128
         that is not hidden_size / heads (q and the gate are 4096 wide
         over a hidden size of 2048), grouped queries (32 over 4), an
         RMSNorm over head_dim on every q and k head, a sigmoid output
         gate on the heads' output before the output projection.
         sliding_attention: rotary over the whole head (rotate-half),
         key j visible to query i iff 0 <= i - j < `sliding_window`.
         full_attention: NO position encoding, causal.
         Built under the name scope `attn_window` or `attn_full`, with
         `core` around the fused_attention op and `attn_gate` around the
         gate's sigmoid and product.
  F_i    i < num_dense_layers: one SwiGLU MLP of `intermediate_size`.
         else Shared(h) + Routed(h).  Routed: one `moe_ffn` op, s =
         sigmoid(h W_r) in f32, the top-k of s + expert_bias, weights the
         unbiased s renormalised over the chosen (+ 1e-20; `route_norm`)
         and multiplied by `route_scale`; `num_local_experts` /
         `expert_offset` build one chip's share of every expert layer
         (the router keeps its width).  Shared: `num_shared_experts` x
         `moe_intermediate_size` wide, under `shared_expert`, computed
         alike on every chip.

The train-program plumbing is `decoder.lm_train_program`;
`trinity_reference.py` is the plain float32 statement of the same
equations.
"""

from .. import framework, layers
from . import transformer as tfm
from .decoder import (EXPERT_BIAS_STD, beside_shared, fc, lm_train_program,
                      norm_or_weight, routed_experts, swiglu_mlp, weight,
                      xent_cost)

__all__ = ["TrinityConfig", "trinity_lm", "trinity_lm_program"]

# expert_bias is a parameter without gradient in the published modeling
# code, zero at initialisation; the rule that moves it in training is the
# trainer's: seeded and balanced as `decoder.EXPERT_BIAS_STD` says.
# what the published router adds to the chosen scores' sum before it divides
_ROUTE_NORM_EPS = 1e-20
# what a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "trinity_eval_rows"
_KINDS = {"sliding_attention": "attn_window", "full_attention": "attn_full"}


class TrinityConfig:
    """Trinity-Mini under the keys of its published config.json; subclass
    to shrink for tests or to cut to a chip's share."""

    vocab_size = 200192
    hidden_size = 2048
    intermediate_size = 6144       # width of the dense layers' MLP
    moe_intermediate_size = 1024   # width of one expert
    num_hidden_layers = 32
    layer_types = (["sliding_attention"] * 3 + ["full_attention"]) * 8
    num_dense_layers = 2
    num_attention_heads = 32
    num_key_value_heads = 4
    head_dim = 128
    sliding_window = 2048
    num_experts = 128              # the router's width
    num_experts_per_tok = 8
    num_shared_experts = 1
    score_func = "sigmoid"
    route_norm = True
    route_scale = 2.826
    n_group = 1
    topk_group = 1
    mup_enabled = True
    rms_norm_eps = 1e-5
    rope_theta = 10000.0
    rope_scaling = None
    max_position_embeddings = 131072
    tie_word_embeddings = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def _check(hp):
    """What the builder would have to guess, it refuses."""
    if len(hp.layer_types) != hp.num_hidden_layers:
        raise ValueError("layer_types names %d layers, num_hidden_layers "
                         "is %d" % (len(hp.layer_types),
                                    hp.num_hidden_layers))
    if hp.n_group != 1 or hp.topk_group != 1:
        raise NotImplementedError(
            "n_group %r / topk_group %r: the router here chooses among all "
            "experts at once" % (hp.n_group, hp.topk_group))
    if hp.score_func != "sigmoid":
        raise NotImplementedError(
            "score_func %r: the router here is sigmoid scores with a "
            "selection bias" % (hp.score_func,))
    if hp.rope_scaling is not None:
        raise NotImplementedError(
            "rope_scaling %r: Trinity-Mini publishes none and this builder "
            "reads none (rotary_embed's scaled frequencies are YaRN's, "
            "which models/laguna.py builds from `rope_parameters`)"
            % (hp.rope_scaling,))
    if hp.tie_word_embeddings:
        raise NotImplementedError("the published head is untied")


def _attention(h, hp, kind, is_test):
    if kind not in _KINDS:
        raise ValueError("layer_types holds %r: neither sliding_attention "
                         "nor full_attention" % (kind,))
    sliding = kind == "sliding_attention"
    with framework.name_scope(_KINDS[kind]):
        return tfm.multi_head_attention(
            h, h, h, None, hp.hidden_size, hp.num_attention_heads,
            is_test=is_test, fused=True, causal=True,
            n_kv_head=hp.num_key_value_heads, rotary=sliding,
            rotary_base=float(hp.rope_theta), qk_norm="head",
            qk_norm_eps=hp.rms_norm_eps, param_attr=norm_or_weight,
            head_dim=hp.head_dim,
            window=int(hp.sliding_window) if sliding else 0,
            out_gate=True, scopes=True)


def _experts(h, hp, is_test):
    routed, _ = routed_experts(
        h, is_test, hp.num_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=hp.route_norm,
        router="sigmoid",
        expert_bias_attr=weight("moe_expert_bias.b", EXPERT_BIAS_STD),
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.route_scale,
        norm_topk_eps=_ROUTE_NORM_EPS)

    def shared(h):
        return swiglu_mlp(h, hp.num_shared_experts * hp.moe_intermediate_size,
                          hp.hidden_size, "shared_ffn")

    return beside_shared(h, routed, shared if hp.num_shared_experts else None)


def _block(x, hp, i, is_test):
    def norm(y, base):
        return layers.rms_norm(y, hp.rms_norm_eps, param_attr=tfm.named(base))

    a = _attention(norm(x, "input_norm.w"), hp, hp.layer_types[i], is_test)
    x = layers.elementwise_add(x, norm(a, "post_attn_norm.w"))
    h = norm(x, "pre_mlp_norm.w")
    m = (swiglu_mlp(h, hp.intermediate_size, hp.hidden_size, "ffn")
         if i < hp.num_dense_layers else _experts(h, hp, is_test))
    return layers.elementwise_add(x, norm(m, "post_mlp_norm.w"))


def trinity_lm(ids, hp=TrinityConfig, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    its own matrix (`tie_word_embeddings` false)."""
    _check(hp)
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    if hp.mup_enabled:
        x = layers.scale(x, scale=float(hp.hidden_size) ** 0.5)
    for i in range(hp.num_hidden_layers):
        x = _block(x, hp, i, is_test)
    x = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("final_norm.w"))
    return fc(x, hp.vocab_size, "softmax_out.w")


def trinity_lm_program(hp=TrinityConfig, seq_len=4096, lr=4e-4,
                       is_test=False, use_bf16=False, mesh=None,
                       bias_rate=None, bias_max_step=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; a training step ends with the selection biases'
    balancing step, as lfm2_lm_program's (`bias_rate` / `bias_max_step`:
    the `expert_bias_update` op's `rate` and `max_step` where given, a
    fine-tuning schedule's); an `is_test` program leaves every token's
    cost in the scope under EVAL_ROWS."""
    return lm_train_program(
        lambda ids, labels: (xent_cost(trinity_lm(ids, hp, is_test), labels),
                             None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS, bias_rate=bias_rate, bias_max_step=bias_max_step)
