"""OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; model type `olmoe`,
https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct): a decoder-only
LM whose every feed-forward is a token-choice mixture of SwiGLU experts.

Block: x += attn(rms(x)); x += moe(rms(x)); a final rms; an untied head.
Attention is the shared `transformer.multi_head_attention` (causal, fused,
RoPE, QK-norm: an rms_norm over the whole q and the whole k projection
before the head split); the experts are one `moe_ffn` op per layer
(softmax router, top-k, dropless); the train-program plumbing is
`decoder.lm_train_program`.  Loss = token cross-entropy +
router_aux_loss_coef * load-balance + router_z_loss_coef * z, the two
router losses summed over the layers.  `olmoe_reference.py` is the plain
float32 statement of the same equations.
"""

import numpy as np

from .. import layers
from . import transformer as tfm
from .decoder import fc, lm_train_program, routed_experts, weight, xent_cost

__all__ = ["OLMoEConfig", "olmoe_lm", "olmoe_lm_program"]


class OLMoEConfig:
    """OLMoE-1B-7B under the keys of its published config.json; subclass
    to shrink for tests."""

    vocab_size = 50304
    hidden_size = 2048
    intermediate_size = 1024  # width of one expert
    num_hidden_layers = 16
    num_attention_heads = 16
    num_key_value_heads = 16
    num_experts = 64
    num_experts_per_tok = 8
    norm_topk_prob = False
    rms_norm_eps = 1e-5
    rope_theta = 10000.0
    max_position_embeddings = 4096
    # the paper's loss weights (its section 4.1); config.json carries a
    # router_aux_loss_coef and no z-loss weight
    router_aux_loss_coef = 0.01
    router_z_loss_coef = 0.001
    partition_family = "gpt2"


def _block(x, hp, is_test):
    d = hp.hidden_size
    h = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("attn_norm.w"))
    a = tfm.multi_head_attention(
        h, h, h, None, d, hp.num_attention_heads, is_test=is_test,
        fused=True, causal=True, n_kv_head=hp.num_key_value_heads,
        rotary=True, rotary_base=float(hp.rope_theta), qk_norm=True,
        qk_norm_eps=hp.rms_norm_eps)
    x = layers.elementwise_add(x, a)
    h = layers.rms_norm(x, hp.rms_norm_eps, param_attr=tfm.named("ffn_norm.w"))
    m, aux = routed_experts(
        h, is_test, hp.num_experts, hp.intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=hp.norm_topk_prob)
    return layers.elementwise_add(x, m), aux


def olmoe_lm(ids, hp=OLMoEConfig, is_test=False):
    """[B, T] token ids -> ([B, T, vocab] next-token logits, the weighted
    router losses summed over the layers as a [1] var)."""
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    coef = layers.assign(np.array(
        [hp.router_aux_loss_coef, hp.router_z_loss_coef], "float32"))
    coef.stop_gradient = True
    router_loss = None
    for _ in range(hp.num_hidden_layers):
        x, aux = _block(x, hp, is_test)
        aux = layers.reduce_sum(layers.elementwise_mul(aux, coef))
        router_loss = (aux if router_loss is None
                       else layers.elementwise_add(router_loss, aux))
    x = layers.rms_norm(x, hp.rms_norm_eps,
                        param_attr=tfm.named("final_norm.w"))
    return fc(x, hp.vocab_size, "softmax_out.w"), router_loss


def olmoe_lm_program(hp=OLMoEConfig, seq_len=4096, lr=4e-4, is_test=False,
                     use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; the loss includes the router losses."""
    def trunk(ids, labels):
        logits, router_loss = olmoe_lm(ids, hp, is_test)
        return xent_cost(logits, labels), router_loss

    return lm_train_program(trunk, seq_len, lr, is_test, use_bf16, mesh,
                            hp.partition_family)
