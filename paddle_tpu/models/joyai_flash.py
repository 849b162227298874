"""JoyAI-LLM-Flash (jdopensource; model type `joyai_llm_flash`,
https://huggingface.co/jdopensource/JoyAI-LLM-Flash): a decoder-only LM of
the DeepSeek-V3 shape, 48B parameters of which 2.7B are active.  Its config
has `deepseek_v3`'s key set, and the family's published modeling code
stands for it: the trunk's block is `decoder.deepseek_v3_block`, the one
kanana-2 stacks (multi-head latent attention; a dense SwiGLU MLP in the
first `first_k_dense_replace` layers, after them one shared expert beside
256 sigmoid-routed ones, top-8).  Two pieces of structure are its own
here:

  a query latent    `q_lora_rank` 1536: q = rms(h W_q_a; own gain) W_q_b
                    (`transformer.latent_attention(q_lora_rank=)`, under
                    the name scope `mla` > `q_latent`).
  a multi-token     `num_nextn_predict_layers` 1: one module of depth 1
  prediction        (DeepSeek-V3 technical report, arXiv:2412.19437,
  module            section 2.2, eq. 21-25) that predicts the token after
                    next from the trunk's state and the NEXT token's
                    embedding, through the trunk's OWN embedding matrix and
                    its OWN head.

With ids[i] = t_i and labels[i] = t_{i+1}, x_L the last trunk block's
output BEFORE the final norm, eps = `rms_norm_eps`:

  trunk    logits_i  = rms(x_L,i; g_f) W_head
           L_main    = sum_i w_i CE(logits_i, labels_i) / sum_i w_i
  module   u_i       = rms(x_L,i; g_h)
           e_i       = rms(Emb[labels_i]; g_e)
           h'_i      = [u_i ; e_i] W_eh,  W_eh [2d, d]
           h''       = Block(h'): one whole block of the expert kind (its
                       own latent attention with its query latent, router,
                       selection bias, shared and routed experts), causal
                       over the same T positions, rotary position i
           logits'_i = rms(h''_i; g_s) W_head
           L_mtp     = sum_i w_{i+1} CE(logits'_i, labels_{i+1})
                       / sum_i w_{i+1}
  loss     L = L_main + `mtp_loss_weight` L_mtp

T stays static: the module runs over all T positions, its targets are
`labels` moved one to the left, and its last position (whose target the
feed does not hold) has weight 0.

The head runs ONCE, over the trunk's rows and the module's stacked along
the time axis, as ouro's does: one `fused_linear_xent` over [B, 2T, d], so
W_head has one gradient and the [d, V] float32 fan-in sum of two is never
made (two ops over one weight would each read W_head and write a gradient
of its size; `linear_xent_fuse_pass` wants logits with one consumer, which
the stacked rows give it).  The embedding is looked up at two sites, the
trunk's under no scope and the module's under `mtp`, and its two
gradients fan in through a `sum`.

Name scopes: `mtp` around the whole module, `mtp` > `combine` around the
two norms, the concat and W_eh; the block's own scopes (`mla` > ...,
`shared_expert`) nest under `mtp`.  A training Program carries `_mtp`,
{"modules": how many were built, "rows": the rows of a sequence the module
scores}.  An `is_test` program leaves every token's cost in the scope
under EVAL_ROWS, [B, 2T]: the trunk's T rows, then the module's T (the
last of them is the cost of the filler target and weighs 0).

The train-program plumbing is `decoder.lm_train_program` (the module's
loss is its scalar `extra`); `joyai_flash_reference.py` is the plain
float32 statement of the same equations.
"""

from .. import framework, layers
from . import transformer as tfm
from .decoder import (deepseek_v3_block, deepseek_v3_check, fc,
                      leave_eval_rows, lm_train_program, weight)

__all__ = ["JoyAIFlashConfig", "joyai_flash_lm_program"]

# what a forward-only program leaves in the scope: every token's
# cross-entropy, the trunk's rows then the module's, [B, 2T] float32
EVAL_ROWS = "joyai_flash_eval_rows"


class JoyAIFlashConfig:
    """JoyAI-LLM-Flash under the keys of its published config.json;
    subclass to shrink for tests or to cut to a chip's share."""

    vocab_size = 129280
    hidden_size = 2048
    intermediate_size = 7168       # width of the dense layer's MLP
    moe_intermediate_size = 768    # width of one expert
    num_hidden_layers = 40
    num_nextn_predict_layers = 1
    first_k_dense_replace = 1
    moe_layer_freq = 1
    num_attention_heads = 32
    num_key_value_heads = 32       # MLA: every head has its own k and v
    kv_lora_rank = 512
    q_lora_rank = 1536
    qk_nope_head_dim = 128
    qk_rope_head_dim = 64
    v_head_dim = 128
    n_routed_experts = 256         # the router's width
    n_shared_experts = 1
    num_experts_per_tok = 8
    n_group = 1
    topk_group = 1
    scoring_func = "sigmoid"
    topk_method = "noaux_tc"
    norm_topk_prob = True
    routed_scaling_factor = 2.5
    rms_norm_eps = 1e-6
    rope_theta = 32000000.0
    rope_interleave = True
    rope_scaling = None
    max_position_embeddings = 131072
    tie_word_embeddings = False
    # the module's loss weight: the config has no key for it; the
    # DeepSeek-V3 report's first-phase value
    mtp_loss_weight = 0.3
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def _check(hp):
    deepseek_v3_check(hp)
    if hp.num_nextn_predict_layers not in (0, 1):
        raise NotImplementedError(
            "num_nextn_predict_layers %r: one module of depth 1 is built "
            "here (how a second would chain is the report's, and no "
            "published config of this family asks for it)"
            % (hp.num_nextn_predict_layers,))


def _moved_left(x, seq_len, filler_scale):
    """x [B, T] -> [x_1 .. x_{T-1}, filler]: the filler is x's own last
    column times `filler_scale` (the batch may be unknown when the program
    is built, so no constant of its shape can be)."""
    last = layers.slice(x, [1], [seq_len - 1], [seq_len])
    if filler_scale != 1.0:
        last = layers.scale(last, filler_scale)
    return layers.concat(
        [layers.slice(x, [1], [1], [seq_len]), last], axis=1)


def _mtp_module(x, labels, emb_attr, hp, is_test):
    """The trunk's state before its final norm and the next tokens -> the
    module's state [B, T, d], normed for the head."""
    d = hp.hidden_size
    with framework.name_scope("mtp"):
        e = layers.embedding(labels, size=[hp.vocab_size, d],
                             param_attr=emb_attr)
        with framework.name_scope("combine"):
            u = layers.rms_norm(x, hp.rms_norm_eps,
                                param_attr=tfm.named("mtp_hnorm.w"))
            e = layers.rms_norm(e, hp.rms_norm_eps,
                                param_attr=tfm.named("mtp_enorm.w"))
            h = fc(layers.concat([u, e], axis=2), d, "mtp_eh_proj.w")
        # a block of the expert kind, whatever the trunk's depth
        h = deepseek_v3_block(h, hp, hp.first_k_dense_replace, is_test)
        return layers.rms_norm(h, hp.rms_norm_eps,
                               param_attr=tfm.named("mtp_final_norm.w"))


def _token_costs(ids, labels, hp, seq_len, is_test):
    """-> ([B, T, 1] cost of every trunk token, lambda L_mtp or None)."""
    _check(hp)
    emb_attr = weight("emb.w")  # ONE parameter, looked up at two sites
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=emb_attr)
    for i in range(hp.num_hidden_layers):
        x = deepseek_v3_block(x, hp, i, is_test)
    rows = layers.rms_norm(x, hp.rms_norm_eps,
                           param_attr=tfm.named("final_norm.w"))
    targets = labels
    if hp.num_nextn_predict_layers:
        # the head once, over both sets of rows stacked along the time
        # axis: one weight gradient
        rows = layers.concat(
            [rows, _mtp_module(x, labels, emb_attr, hp, is_test)], axis=1)
        targets = layers.concat(
            [labels, _moved_left(labels, seq_len, 1.0)], axis=1)
    cost = layers.softmax_with_cross_entropy(
        fc(rows, hp.vocab_size, "softmax_out.w"),
        layers.unsqueeze(targets, [2]))
    if is_test:
        leave_eval_rows(cost, EVAL_ROWS, int(cost.shape[1]))
    if not hp.num_nextn_predict_layers:
        return cost, None
    cost, mtp_cost = layers.split(cost, 2, dim=1)
    # the feed `lm_train_program` made before it called the trunk
    w = _moved_left(ids.block.var("loss_weight"), seq_len, 0.0)
    mtp_loss = layers.elementwise_div(
        layers.reduce_sum(layers.elementwise_mul(
            mtp_cost, layers.unsqueeze(w, [2]))),
        layers.clip(layers.reduce_sum(w), 1e-5, 1e30))
    return cost, layers.scale(mtp_loss, float(hp.mtp_loss_weight))


def joyai_flash_lm_program(hp=JoyAIFlashConfig, seq_len=4096, lr=5e-6,
                           is_test=False, use_bf16=False, mesh=None,
                           bias_rate=None, bias_max_step=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them: the loss is L_main + mtp_loss_weight L_mtp, the token
    count the trunk's; a training step ends with the selection biases'
    balancing step over every `moe_ffn`, the module's among them
    (`bias_rate` / `bias_max_step`: the `expert_bias_update` op's `rate`
    and `max_step` where given); an `is_test` program leaves every token's
    cost in the scope under EVAL_ROWS."""
    out = lm_train_program(
        lambda ids, labels: _token_costs(ids, labels, hp, seq_len, is_test),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        bias_rate=bias_rate, bias_max_step=bias_max_step)
    if hp.num_nextn_predict_layers:
        out[0]._mtp = {"modules": int(hp.num_nextn_predict_layers),
                       "rows": seq_len - 1}
    return out
