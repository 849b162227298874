"""kanana-2-30b-a3b (kakaocorp; model type `deepseek_v3`,
https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601): a
decoder-only LM of the DeepSeek-V3 shape.  Every layer mixes tokens with
multi-head latent attention (`transformer.latent_attention`: a 512-wide
key/value latent all heads share, a decoupled 64-wide rotary part, scores
192 wide over 128-wide values); its feed-forward is a dense SwiGLU MLP in
the first `first_k_dense_replace` layers and, after them, shared experts
every token passes through beside a token-choice mixture of routed ones.

Block i: x += MLA(rms(x)); x += F_i(rms(x)); a final rms; an untied head.
No bias anywhere.

  dense    one SwiGLU MLP of `intermediate_size`.
  experts  Shared(h) + Routed(h).
           Routed: one `moe_ffn` op, the `noaux_tc` router with one group:
           s = sigmoid(h W_r) in f32, the top-k of s +
           e_score_correction_bias, weights the unbiased s renormalised
           over the chosen (+ 1e-20) and multiplied by
           `routed_scaling_factor`; no auxiliary loss; a training program
           moves the bias against the load after every step
           (`expert_bias_update`, as lfm2's).  `num_local_experts` /
           `expert_offset` build one chip's share of every expert layer
           (the router keeps its width).
           Shared: the `n_shared_experts` shared experts are ONE SwiGLU
           MLP of n_shared_experts x moe_intermediate_size (the published
           code builds them so), computed alike on every chip.

The block is `decoder.deepseek_v3_block` (the family's published modeling
code; `models/joyai_flash.py` builds its trunk and its prediction module
from the same function), the train-program plumbing
`decoder.lm_train_program`; `kanana2_reference.py` is the plain float32
statement of the same equations.
"""

from .. import layers
from . import transformer as tfm
from .decoder import (deepseek_v3_block, deepseek_v3_check, fc,
                      lm_train_program, weight, xent_cost)

__all__ = ["Kanana2Config", "kanana2_lm", "kanana2_lm_program"]

# (e_score_correction_bias is a buffer in the published modeling code, zero
# at initialisation, and the training rule that moves it is not in the
# config: seeded and balanced as `decoder.EXPERT_BIAS_STD` says.)
# What a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "kanana2_eval_rows"


class Kanana2Config:
    """kanana-2-30b-a3b-instruct-2601 under the keys of its published
    config.json; subclass to shrink for tests or to cut to a chip's
    share."""

    vocab_size = 128256
    hidden_size = 2048
    intermediate_size = 6144       # width of the dense layers' MLP
    moe_intermediate_size = 768    # width of one expert
    num_hidden_layers = 48
    first_k_dense_replace = 1
    moe_layer_freq = 1
    num_attention_heads = 32
    num_key_value_heads = 32       # MLA: every head has its own k and v
    kv_lora_rank = 512
    q_lora_rank = None
    qk_nope_head_dim = 128
    qk_rope_head_dim = 64
    v_head_dim = 128
    n_routed_experts = 128         # the router's width
    n_shared_experts = 2
    num_experts_per_tok = 6
    n_group = 1
    topk_group = 1
    scoring_func = "sigmoid"
    topk_method = "noaux_tc"
    norm_topk_prob = True
    routed_scaling_factor = 2.448
    rms_norm_eps = 1e-6
    rope_theta = 1000000.0
    rope_interleave = True
    rope_scaling = None
    max_position_embeddings = 32768
    tie_word_embeddings = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def kanana2_lm(ids, hp=Kanana2Config, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    its own matrix (`tie_word_embeddings` false)."""
    deepseek_v3_check(hp)
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    for i in range(hp.num_hidden_layers):
        x = deepseek_v3_block(x, hp, i, is_test)
    x = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("final_norm.w"))
    return fc(x, hp.vocab_size, "softmax_out.w")


def kanana2_lm_program(hp=Kanana2Config, seq_len=4096, lr=4e-4,
                       is_test=False, use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; a training step ends with the selection biases'
    balancing step, as lfm2_lm_program's; an `is_test` program leaves
    every token's cost in the scope under EVAL_ROWS."""
    return lm_train_program(
        lambda ids, labels: (xent_cost(kanana2_lm(ids, hp, is_test), labels),
                             None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS)
