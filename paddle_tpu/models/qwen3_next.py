"""Qwen3-Next-80B-A3B (Qwen; model type `qwen3_next`,
https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct): a decoder-only
LM whose token mixers are of two kinds in one stack, Gated DeltaNet
(linear attention: the delta rule with ONE decay a head) and gated softmax
attention, layer i (1-based) full attention where i %
`full_attention_interval` == 0 (three to one as published), and whose
feed-forward is in every layer a gated shared expert beside a
token-choice mixture of routed ones.

Block i: x += Mixer_i(rms(x)); x += F(rms(x)); a final rms; an untied
head.  Every rms of the model but the one inside the GDN mixer has the
gain 1 + w, w zero at initialisation (`Qwen3NextRMSNorm`).  No bias on any
projection.

  GDN   Gated DeltaNet, under the name scope `gdn`:
        proj  [q | k | v] = h W_qkv (key heads x key dim, twice, then
              value heads x value dim), z = h W_z (the output gate), b =
              h W_b and a = h W_a (a number a value head each)
        conv  ONE depthwise causal convolution of `linear_conv_kernel_dim`
              taps and SiLU over the concatenated q, k, v channels
              (`causal_conv`), then an L2 norm over every q and k head
        gate  g = -exp(A_log[head]) softplus(a + dt_bias[head]), the
              log-decay of a value HEAD, and beta = sigmoid(b), float32
        core  one `gated_delta_attention` op (ops/kda_ops.py): per value
              head a key dim x value dim state, decayed by one number,
              corrected by the delta rule, read by q key_dim^-0.5; value
              head j reads key head j // (value heads / key heads)
        out   a plain-gain RMSNorm over every head's output times SiLU of
              z (the published Qwen3NextRMSNormGated), then W_o
  Attn  the shared `transformer.multi_head_attention` under `attn_full`:
        `head_dim` 256, grouped queries (16 over 2), a 1 + w RMSNorm over
        head_dim on every q and k head, rotary (rotate-half) on the head's
        first `partial_rotary_factor` lanes under `rope`, a sigmoid output
        gate on the heads' output before the output projection.
  F     sigmoid(h w_sg) Shared(h) + Routed(h).  Routed: one `moe_ffn` op,
        p = softmax(h W_r) in f32 over all `num_experts`, the top-k,
        weights renormalised over the chosen (`norm_topk_prob`);
        `num_local_experts` / `expert_offset` build one chip's share of
        every expert layer (the router keeps its width).  Shared:
        `shared_expert_intermediate_size` wide, under `shared_expert`
        with its gate (a number a token), computed alike on every chip.

The published module list also holds a multi-token prediction head in the
checkpoint's description; `Qwen3NextForCausalLM` builds none and none is
built here.  The train-program plumbing is `decoder.lm_train_program`;
`qwen3_next_reference.py` is the plain float32 statement of the same
equations, with Gated DeltaNet as the token-by-token recurrence.
"""

from .. import framework, layers
from ..param_attr import ParamAttr
from . import transformer as tfm
from .decoder import (A_RANGE, DT_RANGE, L2_EPS, InverseSoftplusOfLogUniform,
                      LogUniform, beside_shared, fc, lm_train_program,
                      norm_or_weight, routed_experts, swiglu_mlp, weight,
                      xent_cost)

__all__ = ["Qwen3NextConfig", "qwen3_next_lm", "qwen3_next_lm_program"]

# what a forward-only program leaves in the scope: every token's
# cross-entropy, [B, T] float32 (an evaluation pairs it with a reference's)
EVAL_ROWS = "qwen3_next_eval_rows"


class Qwen3NextConfig:
    """Qwen3-Next-80B-A3B-Instruct under the keys of its published
    config.json; subclass to shrink for tests or to cut to a chip's
    share."""

    vocab_size = 151936
    hidden_size = 2048
    num_hidden_layers = 48
    full_attention_interval = 4
    linear_num_key_heads = 16
    linear_num_value_heads = 32
    linear_key_head_dim = 128
    linear_value_head_dim = 128
    linear_conv_kernel_dim = 4
    num_attention_heads = 16
    num_key_value_heads = 2
    head_dim = 256
    partial_rotary_factor = 0.25
    rope_theta = 10000000.0
    rope_scaling = None
    num_experts = 512              # the router's width
    num_experts_per_tok = 10
    norm_topk_prob = True
    moe_intermediate_size = 512    # width of one routed expert
    shared_expert_intermediate_size = 512
    decoder_sparse_step = 1
    mlp_only_layers = ()
    hidden_act = "silu"
    rms_norm_eps = 1e-6
    tie_word_embeddings = False
    use_sliding_window = False
    # a chip's share of every expert layer: None holds all the experts
    num_local_experts = None
    expert_offset = 0
    partition_family = "gpt2"


def mixer_of(hp, i):
    """"gdn" or "attn" for layer i (0-based; the published rule counts
    from 1)."""
    return "attn" if (i + 1) % int(hp.full_attention_interval) == 0 else "gdn"


def _check(hp):
    """What the builder would have to guess, it refuses."""
    if hp.decoder_sparse_step != 1 or tuple(hp.mlp_only_layers):
        raise NotImplementedError(
            "decoder_sparse_step %r / mlp_only_layers %r: every layer is an "
            "expert layer here" % (hp.decoder_sparse_step,
                                   hp.mlp_only_layers))
    if hp.linear_num_value_heads % hp.linear_num_key_heads:
        raise ValueError(
            "linear_num_key_heads %d does not divide linear_num_value_heads "
            "%d" % (hp.linear_num_key_heads, hp.linear_num_value_heads))
    if hp.rope_scaling is not None:
        raise NotImplementedError(
            "rope_scaling %r: Qwen3-Next publishes none and this builder reads "
            "none (rotary_embed's scaled frequencies are YaRN's)"
            % (hp.rope_scaling,))
    if hp.use_sliding_window:
        raise NotImplementedError("the published attention layers are full")
    if hp.hidden_act != "silu":
        raise NotImplementedError("hidden_act %r: the experts are SwiGLU"
                                  % (hp.hidden_act,))
    if hp.tie_word_embeddings:
        raise NotImplementedError("the published head is untied")


def _norm(x, hp, base):
    """The model's RMSNorm: the gain 1 + w, w zero at initialisation."""
    return layers.rms_norm(x, hp.rms_norm_eps, param_attr=tfm.named(base),
                           unit_offset=True)


def _gdn(h, hp):
    """h [B, T, d] -> [B, T, d]: one Gated DeltaNet mixer."""
    hk, hv = int(hp.linear_num_key_heads), int(hp.linear_num_value_heads)
    dk, dv = int(hp.linear_key_head_dim), int(hp.linear_value_head_dim)
    b, t = h.shape[0], h.shape[1]

    def lead(y, heads, width):  # [B, T, heads width] -> [B, heads, T, width]
        return layers.transpose(layers.reshape(y, [b, t, heads, width]),
                                [0, 2, 1, 3])

    with framework.name_scope("gdn"):
        with framework.name_scope("proj"):
            qkv = fc(h, 2 * hk * dk + hv * dv, "gdn_qkv.w")
            z = fc(h, hv * dv, "gdn_z.w")
            beta = fc(h, hv, "gdn_b.w")
            a = fc(h, hv, "gdn_a.w")  # dt_bias joins it under `gate`
            dt_bias = layers.create_parameter(
                [hv], "float32",
                attr=ParamAttr(
                    name=framework.unique_name.generate("gdn_dt.b"),
                    initializer=InverseSoftplusOfLogUniform(*DT_RANGE)))
        with framework.name_scope("conv"):
            qkv = layers.causal_conv(
                qkv, int(hp.linear_conv_kernel_dim), act="silu",
                param_attr=weight("gdn_conv.w"))
            q, k, v = layers.split(qkv, [hk * dk, hk * dk, hv * dv], dim=-1)
            q, k = (layers.l2_normalize(
                layers.reshape(y, [b, t, hk, dk]), axis=-1, epsilon=L2_EPS)
                for y in (q, k))
            q, k = (layers.transpose(y, [0, 2, 1, 3]) for y in (q, k))
            v = lead(v, hv, dv)
        with framework.name_scope("gate"):
            a_log = layers.create_parameter(
                [hv], "float32",
                attr=ParamAttr(
                    name=framework.unique_name.generate("gdn_A_log.w"),
                    initializer=LogUniform(*A_RANGE)))
            # in the op's layout, [B, heads, T]; dt_bias is added to the
            # projection HERE, after a cast to float32 that says so: as
            # the projection's own bias, or added to its bfloat16 result,
            # the AMP pass rounds the sum to bfloat16, a grid of 0.03 near
            # -7: up to 1.5 % of the log-decay of a whole head, where a
            # decay of every channel averages such rounding over 128
            # (PERF.md section 6, PR 48: at the initial weights the costs'
            # distance from the reference falls by an eighth; after 120
            # steps on the chip it reads the same either way)
            g = layers.elementwise_mul(
                layers.softplus(layers.elementwise_add(
                    layers.cast(layers.transpose(a, [0, 2, 1]), "float32"),
                    dt_bias, axis=1)),
                layers.scale(layers.exp(a_log), scale=-1.0), axis=1)
            beta = layers.transpose(layers.sigmoid(beta), [0, 2, 1])
        with framework.name_scope("core"):
            o = layers.gated_delta_attention(q, k, v, g, beta)
        with framework.name_scope("out"):
            o = layers.rms_norm(layers.transpose(o, [0, 2, 1, 3]),
                                hp.rms_norm_eps,
                                param_attr=tfm.named("gdn_o_norm.w"))
            o = layers.elementwise_mul(
                o, layers.swish(layers.reshape(z, [b, t, hv, dv])))
            return fc(layers.reshape(o, [b, t, hv * dv]), hp.hidden_size,
                      "gdn_o.w")


def _attention(h, hp, is_test):
    with framework.name_scope("attn_full"):
        return tfm.multi_head_attention(
            h, h, h, None, hp.hidden_size, hp.num_attention_heads,
            is_test=is_test, fused=True, causal=True,
            n_kv_head=hp.num_key_value_heads, rotary=True,
            rotary_base=float(hp.rope_theta), qk_norm="head",
            qk_norm_eps=hp.rms_norm_eps, param_attr=norm_or_weight,
            head_dim=hp.head_dim, out_gate=True, scopes=True,
            rotary_dim=int(hp.head_dim * hp.partial_rotary_factor),
            norm_unit_offset=True)


def _experts(h, hp, is_test):
    routed, _ = routed_experts(
        h, is_test, hp.num_experts, hp.moe_intermediate_size,
        hp.num_experts_per_tok, norm_topk_prob=hp.norm_topk_prob,
        router="softmax", num_local_experts=hp.num_local_experts,
        expert_offset=hp.expert_offset)

    def shared(h):
        return layers.elementwise_mul(
            swiglu_mlp(h, hp.shared_expert_intermediate_size, hp.hidden_size,
                       "shared_ffn"),
            layers.sigmoid(fc(h, 1, "shared_expert_gate.w")))

    return beside_shared(h, routed, shared)


def _block(x, hp, i, is_test):
    h = _norm(x, hp, "attn_norm.w")
    a = _gdn(h, hp) if mixer_of(hp, i) == "gdn" else _attention(h, hp,
                                                               is_test)
    x = layers.elementwise_add(x, a)
    return layers.elementwise_add(
        x, _experts(_norm(x, hp, "ffn_norm.w"), hp, is_test))


def qwen3_next_lm(ids, hp=Qwen3NextConfig, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits; the head is
    its own matrix (`tie_word_embeddings` false)."""
    _check(hp)
    x = layers.embedding(ids, size=[hp.vocab_size, hp.hidden_size],
                         param_attr=weight("emb.w"))
    for i in range(hp.num_hidden_layers):
        x = _block(x, hp, i, is_test)
    return fc(_norm(x, hp, "final_norm.w"), hp.vocab_size, "softmax_out.w")


def qwen3_next_lm_program(hp=Qwen3NextConfig, seq_len=8192, lr=5e-6,
                          is_test=False, use_bf16=False, mesh=None):
    """(main, startup, feeds, [loss, token_count]) as gpt2_lm_program
    returns them; an `is_test` program leaves every token's cost in the
    scope under EVAL_ROWS.  The router has no selection bias and the step
    no balancing op (the published model has neither)."""
    return lm_train_program(
        lambda ids, labels: (
            xent_cost(qwen3_next_lm(ids, hp, is_test), labels), None),
        seq_len, lr, is_test, use_bf16, mesh, hp.partition_family,
        eval_rows=EVAL_ROWS)
