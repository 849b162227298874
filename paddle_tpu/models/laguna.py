"""Laguna-XS.2 (poolside; model type `laguna`,
https://huggingface.co/poolside/Laguna-XS.2): a decoder-only LM whose
attention layers are of two kinds that differ in MORE than their mask: the
config's per-layer lists give every layer its kind (`layer_types`: one
`full_attention` to three `sliding_attention`), its number of query heads
(`num_attention_heads_per_layer`: 48 on the full layers, 64 on the sliding
ones, over 8 KV heads of 128 on both) and its feed-forward
(`mlp_layer_types`: layer 0 `dense`, the others `sparse`), and
`rope_parameters` gives each KIND its rotary.

Block l, two RMSNorms, both on a branch's input:

    x += Attn_l(rms(x));  x += F_l(rms(x))

a final rms; an untied head; the embedding unscaled.  No bias anywhere.

  Attn   the shared `transformer.multi_head_attention` at H_l query heads
         (q and o are H_l x 128 wide over a hidden size of 2048), grouped
         queries (H_l / 8 a KV head: 8 or 6), no QK-norm, ONE sigmoid gate
         a head and token on the heads' output before the output
         projection (`gating`; the gate's projection is [d, H_l]).
         sliding_attention: rotary over the whole head at theta 10,000,
         key j visible to query i iff 0 <= i - j < `sliding_window`.
         full_attention: causal; rotary on the head's first
         `partial_rotary_factor` x head_dim lanes alone, its inverse
         frequencies YaRN's (`rope_type` "yarn": theta 500,000, `factor`
         over `original_max_position_embeddings`, `beta_fast` /
         `beta_slow`) and cos and sin times `attention_factor`.
         Built under the name scope `attn_window` or `attn_full`, with
         `core` around the fused_attention op, `rope` around the turn and
         `attn_gate` around the gate's projection, sigmoid and product.
  F_l    dense: one SwiGLU MLP of `intermediate_size`.  sparse: Shared(h) +
         Routed(h).  Routed: one `moe_ffn` op, s = sigmoid(h W_r) in f32,
         the top-k of s (NO selection bias: no key names one), weights s
         renormalised over the chosen (+ 1e-20) and multiplied by
         `moe_routed_scaling_factor`; `num_local_experts` / `expert_offset`
         build one chip's share of every expert layer (the router keeps
         its width).  Shared: one SwiGLU MLP of
         `shared_expert_intermediate_size`, under `shared_expert`, computed
         alike on every chip.

What `config.json` names without its form, or is silent on (the gate's
form, the router's score, no QK-norm, the norms' places, SiLU), is a
READING, made under one rule and listed with its evidence in
`benchmark/configs/laguna_xs2_33b_a3b.json`'s `assumed`: the published
`modeling_laguna.py` was not at hand.

The train-program plumbing is `decoder.lm_train_program`;
`laguna_reference.py` is the plain float32 statement of the same equations.
"""

from .. import framework, layers
from . import transformer as tfm
from .decoder import (NORM_TOPK_EPS, beside_shared, fc, lm_train_program,
                      norm_or_weight, routed_experts, swiglu_mlp, weight,
                      xent_cost)

__all__ = ["LagunaConfig", "laguna_lm", "laguna_lm_program"]

# what a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "laguna_eval_rows"
_KINDS = {"sliding_attention": "attn_window", "full_attention": "attn_full"}
_PERIOD = ["full_attention"] + ["sliding_attention"] * 3


class LagunaConfig:
    """Laguna-XS.2 under the keys of its published config.json; subclass
    to shrink for tests or to cut to a chip's share."""

    vocab_size = 100352
    hidden_size = 2048
    intermediate_size = 8192               # width of the dense layer's MLP
    moe_intermediate_size = 512            # width of one expert
    shared_expert_intermediate_size = 512
    num_hidden_layers = 40
    layer_types = _PERIOD * 10
    mlp_layer_types = ["dense"] + ["sparse"] * 39
    num_attention_heads_per_layer = [48, 64, 64, 64] * 10
    num_key_value_heads = 8
    head_dim = 128
    sliding_window = 512
    rope_parameters = {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096,
    }
    gating = True
    num_experts = 256                      # the router's width
    num_experts_per_tok = 8
    moe_routed_scaling_factor = 2.5
    moe_apply_router_weight_on_input = False
    attention_bias = False
    rms_norm_eps = 1e-6
    max_position_embeddings = 262144
    tie_word_embeddings = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def _check(hp):
    """What the builder would have to guess, it refuses."""
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        if len(getattr(hp, key)) != hp.num_hidden_layers:
            raise ValueError("%s names %d layers, num_hidden_layers is %d"
                             % (key, len(getattr(hp, key)),
                                hp.num_hidden_layers))
    for kind in set(hp.layer_types):
        if kind not in _KINDS:
            raise ValueError("layer_types holds %r: neither "
                             "sliding_attention nor full_attention" % (kind,))
        rope = hp.rope_parameters.get(kind)
        if rope is None or rope.get("rope_type") not in ("default", "yarn"):
            raise NotImplementedError(
                "rope_parameters[%r] is %r: rotary here is `default` or "
                "`yarn`" % (kind, rope))
    for kind in set(hp.mlp_layer_types):
        if kind not in ("dense", "sparse"):
            raise ValueError("mlp_layer_types holds %r: neither dense nor "
                             "sparse" % (kind,))
    for heads in set(hp.num_attention_heads_per_layer):
        if heads % hp.num_key_value_heads:
            raise ValueError(
                "num_key_value_heads %d does not divide a layer's %d query "
                "heads" % (hp.num_key_value_heads, heads))
    if hp.gating not in (True, False):
        raise NotImplementedError(
            "gating %r: the gate here is one sigmoid a head (true) or none"
            % (hp.gating,))
    if hp.attention_bias:
        raise NotImplementedError("the published projections have no bias")
    if hp.moe_apply_router_weight_on_input:
        raise NotImplementedError(
            "moe_apply_router_weight_on_input: the router's weight "
            "multiplies an expert's output here")
    if hp.tie_word_embeddings:
        raise NotImplementedError("the published head is untied")


def _attention(h, hp, i, is_test):
    kind = hp.layer_types[i]
    rope = hp.rope_parameters[kind]
    sliding = kind == "sliding_attention"
    scaling = None
    if rope["rope_type"] == "yarn":
        scaling = {k: v for k, v in rope.items()
                   if k not in ("rope_theta", "partial_rotary_factor")}
    with framework.name_scope(_KINDS[kind]):
        return tfm.multi_head_attention(
            h, h, h, None, hp.hidden_size,
            hp.num_attention_heads_per_layer[i], is_test=is_test, fused=True,
            causal=True, n_kv_head=hp.num_key_value_heads, rotary=True,
            rotary_base=float(rope["rope_theta"]), param_attr=norm_or_weight,
            head_dim=hp.head_dim,
            window=int(hp.sliding_window) if sliding else 0,
            out_gate="head" if hp.gating else False, scopes=True,
            rotary_dim=int(hp.head_dim
                           * rope.get("partial_rotary_factor", 1)),
            rotary_scaling=scaling)


def _experts(h, hp, is_test):
    routed, _ = routed_experts(
        h, is_test, hp.num_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=True, router="sigmoid",
        num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset,
        routed_scaling_factor=hp.moe_routed_scaling_factor,
        norm_topk_eps=NORM_TOPK_EPS)

    def shared(h):
        return swiglu_mlp(h, hp.shared_expert_intermediate_size,
                          hp.hidden_size, "shared_ffn")

    return beside_shared(
        h, routed, shared if hp.shared_expert_intermediate_size else None)


def _block(x, hp, i, is_test):
    def norm(y, base):
        return layers.rms_norm(y, hp.rms_norm_eps, param_attr=tfm.named(base))

    x = layers.elementwise_add(
        x, _attention(norm(x, "input_norm.w"), hp, i, is_test))
    h = norm(x, "pre_mlp_norm.w")
    m = (swiglu_mlp(h, hp.intermediate_size, hp.hidden_size, "ffn")
         if hp.mlp_layer_types[i] == "dense" else _experts(h, hp, is_test))
    return layers.elementwise_add(x, m)


def laguna_lm(ids, hp=LagunaConfig, is_test=False):
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


def laguna_lm_program(hp=LagunaConfig, seq_len=4096, lr=4e-4, is_test=False,
                      use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; no router carries a selection bias, so a training step
    ends with the optimizer; an `is_test` program leaves every token's cost
    in the scope under EVAL_ROWS."""
    return lm_train_program(
        lambda ids, labels: (xent_cost(laguna_lm(ids, hp, is_test), labels),
                             None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS)
