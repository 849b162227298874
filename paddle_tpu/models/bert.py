"""BERT-base pretraining model (BASELINE.json config 3: "BERT-base
pretraining (fluid ops, Pallas fused attention, DP)").

Encoder-only transformer built from the same blocks (and the same
TP-rule-compatible parameter names) as models/transformer.py: token +
position + segment embeddings -> N post-LN encoder layers -> masked-LM
head over every position (masked positions selected by a weight feed — the
static-shape TPU form of the gather-based MLM head) + next-sentence head
on the [CLS] vector.  hp.fused_attn routes attention through the
fused/flash kernel with the rank-1 key-padding bias.
"""

import numpy as np

from .. import layers, unique_name
from ..initializer import Normal
from ..param_attr import ParamAttr
from . import transformer as tfm

__all__ = ["BertConfig", "bert_encoder", "bert_pretrain_program", "make_fake_bert_batch"]


class BertConfig:
    """bert-base shape defaults; subclass to shrink for tests."""

    vocab_size = 30522
    type_vocab_size = 2
    max_position = 512
    d_model = 768
    d_inner_hid = 3072
    n_head = 12
    n_layer = 12
    dropout = 0.1
    fused_attn = False
    recompute = False  # rematerialize each encoder layer in backward
    label_smooth_eps = 0.0  # encoder reuses tfm blocks; unused here
    partition_family = "bert"


def _emb_table(name):
    return ParamAttr(
        name=unique_name.generate(name), initializer=Normal(0.0, 0.02)
    )


def bert_encoder(src_ids, seg_ids, attn_bias, hp, is_test=False, kpad_bias=None):
    """[B, T] ids -> [B, T, d_model] sequence output."""
    tok = layers.embedding(
        src_ids, size=[hp.vocab_size, hp.d_model],
        param_attr=_emb_table("emb.w"),
    )
    seg = layers.embedding(
        seg_ids, size=[hp.type_vocab_size, hp.d_model],
        param_attr=_emb_table("seg_emb.w"),
    )
    # learned position table (BERT uses trained positions, not sinusoids)
    pos_table = layers.create_parameter(
        shape=[hp.max_position, hp.d_model],
        dtype="float32",
        attr=_emb_table("pos_emb.w"),
    )
    seq_len = src_ids.shape[1]
    pos = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq_len])
    x = layers.elementwise_add(
        layers.elementwise_add(tok, seg), pos, axis=1
    )
    x = layers.layer_norm(x, begin_norm_axis=2)
    if hp.dropout and not is_test:
        x = layers.dropout(x, hp.dropout, is_test=is_test)
    for _ in range(hp.n_layer):
        if getattr(hp, "recompute", False) and not is_test:
            x = layers.recompute(
                lambda h: tfm.encoder_layer(
                    h, attn_bias, hp, is_test, kpad_bias=kpad_bias
                ),
                x,
            )
        else:
            x = tfm.encoder_layer(x, attn_bias, hp, is_test,
                                  kpad_bias=kpad_bias)
    return x


def bert_pretrain_program(hp=BertConfig, seq_len=128, lr=1e-4, is_test=False,
                          use_bf16=False, mesh=None):
    """Build (main, startup, feeds, [total, mlm, nsp]) for MLM+NSP
    pretraining.  Feeds:
      src_ids/seg_ids [B, T] int64; input_mask [B, T] float (1 = real);
      mlm_labels [B, T] int64 (label at masked slots, anything elsewhere);
      mlm_weight [B, T] float (1 at masked slots);
      nsp_label [B, 1] int64.
    """
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        src = layers.data("src_ids", shape=[seq_len], dtype="int64")
        seg = layers.data("seg_ids", shape=[seq_len], dtype="int64")
        mask = layers.data("input_mask", shape=[seq_len], dtype="float32")
        mlm_lbl = layers.data("mlm_labels", shape=[seq_len], dtype="int64")
        mlm_w = layers.data("mlm_weight", shape=[seq_len], dtype="float32")
        nsp_lbl = layers.data("nsp_label", shape=[1], dtype="int64")

        # additive key bias from the mask: 0 at real tokens, -1e9 at pads
        kpad = layers.scale(mask, scale=1e9, bias=-1e9)
        kpad.stop_gradient = True
        if getattr(hp, "fused_attn", False):
            attn_bias, kpad_bias = None, kpad
        else:
            attn_bias = layers.unsqueeze(layers.unsqueeze(kpad, [1]), [1])
            kpad_bias = None

        enc = bert_encoder(src, seg, attn_bias, hp, is_test, kpad_bias)

        # masked-LM head: transform + vocab logits at EVERY position,
        # loss weighted to the masked slots (static shapes; the gather
        # form of the original would be dynamic)
        mlm_h = layers.fc(enc, size=hp.d_model, num_flatten_dims=2,
                          act="gelu", param_attr=_emb_table("mlm_trans.w"))
        mlm_h = layers.layer_norm(mlm_h, begin_norm_axis=2)
        mlm_logits = layers.fc(
            mlm_h, size=hp.vocab_size, num_flatten_dims=2, bias_attr=False,
            param_attr=_emb_table("softmax_out.w"),
        )
        mlm_cost = layers.softmax_with_cross_entropy(
            mlm_logits, layers.unsqueeze(mlm_lbl, [2])
        )
        mlm_cost = layers.elementwise_mul(mlm_cost, layers.unsqueeze(mlm_w, [2]))
        # epsilon-guarded denominator: a batch with zero masked slots must
        # yield loss 0, not 0/0 = NaN poisoning every weight
        denom = layers.clip(layers.reduce_sum(mlm_w), 1e-5, 1e30)
        mlm_loss = layers.elementwise_div(
            layers.reduce_sum(mlm_cost), denom
        )

        # next-sentence head on [CLS] (position 0)
        cls = layers.squeeze(layers.slice(enc, axes=[1], starts=[0], ends=[1]), [1])
        pooled = layers.fc(cls, size=hp.d_model, act="tanh",
                           param_attr=_emb_table("pooler.w"))
        nsp_logits = layers.fc(pooled, size=2,
                               param_attr=_emb_table("nsp.w"))
        nsp_loss = layers.mean(
            layers.softmax_with_cross_entropy(nsp_logits, nsp_lbl)
        )
        total = layers.elementwise_add(mlm_loss, nsp_loss)

        # logits-free MLM loss (fused_linear_xent lowers to
        # linear_xent_tiled: the [B, T, V] f32 logits exist a vocabulary
        # tile at a time) + the fc / fused_residual_ln ops, applied
        # before minimize so grads differentiate through the fused ops
        from ..transpiler.pass_registry import apply_pass

        apply_pass(main, "linear_xent_fuse_pass")
        apply_pass(main, "matmul_epilogue_fuse_pass")
        if use_bf16:
            apply_pass(main, "bf16_amp_pass")
        # HBM-budgeted remat (FLAGS_hbm_budget_bytes; no-op when unset);
        # the flag is a per-device budget, so a mesh scales it
        from ..transpiler.remat import maybe_remat

        maybe_remat(main, total, is_test, mesh=mesh)
        if not is_test:
            fluid.optimizer.Adam(learning_rate=lr).minimize(total)

    if mesh is not None:
        # GSPMD training stamp: bert-family rules lifted to training
        # names (grads + Adam moments shard like their param), batch
        # feeds over the mesh's dp axis
        from ..parallel.partition_rules import (annotate_spmd,
                                                train_partition_rules_for)

        annotate_spmd(main, mesh, train_partition_rules_for(
            getattr(hp, "partition_family", "bert")))
    feeds = ["src_ids", "seg_ids", "input_mask", "mlm_labels", "mlm_weight",
             "nsp_label"]
    return main, startup, feeds, [total, mlm_loss, nsp_loss]


def make_fake_bert_batch(batch_size, seq_len, hp=BertConfig, seed=0,
                         mask_frac=0.15):
    rng = np.random.RandomState(seed)
    src = rng.randint(3, hp.vocab_size, (batch_size, seq_len)).astype("int64")
    lens = rng.randint(seq_len // 2, seq_len + 1, (batch_size,))
    mask = (np.arange(seq_len)[None, :] < lens[:, None]).astype("float32")
    seg_split = rng.randint(1, seq_len, (batch_size,))
    seg = (np.arange(seq_len)[None, :] >= seg_split[:, None]).astype("int64")
    mlm_w = (rng.rand(batch_size, seq_len) < mask_frac).astype("float32") * mask
    mlm_w[:, 0] = 1.0  # guarantee at least one masked slot per row
    labels = src.copy()
    src = np.where(mlm_w > 0, 1, src)  # [MASK] id = 1
    nsp = rng.randint(0, 2, (batch_size, 1)).astype("int64")
    return {
        "src_ids": src, "seg_ids": seg, "input_mask": mask,
        "mlm_labels": labels, "mlm_weight": mlm_w, "nsp_label": nsp,
    }
