"""Train a small causal LM on PACKED ragged sequences, end to end:

    python examples/packed_training.py

Ragged token sequences (lengths 3..14) pack into fixed [N, 16] rows
(`reader.pack_sequences`) — ~2x fewer rows than one-per-sequence
padding.  Per-token segment ids keep attention within each original
sequence (`fused_attention(segment_ids=...)`; the flash kernels where
platform and shape engage them), per-segment positions index the
position table, and
the loss masks padding (`segment_ids > 0`).  One compiled shape serves
the whole ragged stream: the TPU-form of the reference's LoD
no-padding efficiency.
"""

import numpy as np

import paddle_tpu as fluid
from paddle_tpu import layers
from paddle_tpu.reader import pack_sequences

VOCAB, L, D, HEADS = 40, 16, 32, 4


def build(n_rows):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        tokens = layers.data("tokens", shape=[n_rows, L], dtype="int64",
                             append_batch_size=False)
        seg = layers.data("seg", shape=[n_rows, L], dtype="int32",
                          append_batch_size=False)
        pos = layers.data("pos", shape=[n_rows, L], dtype="int64",
                          append_batch_size=False)
        labels = layers.data("labels", shape=[n_rows, L], dtype="int64",
                             append_batch_size=False)

        emb = layers.embedding(tokens, size=[VOCAB, D])
        # positions restart per packed segment -> gather rows of the
        # position table by the PACKED positions, not the row positions
        pos_table = layers.create_parameter(shape=[L, D], dtype="float32")
        pos_emb = layers.reshape(
            layers.gather(pos_table, layers.reshape(pos, [n_rows * L])),
            [n_rows, L, D])
        x = layers.elementwise_add(emb, pos_emb)
        qkv = layers.reshape(
            layers.fc(x, size=3 * D, num_flatten_dims=2, bias_attr=False),
            [n_rows, L, 3, HEADS, D // HEADS])
        qkv = layers.transpose(qkv, [2, 0, 3, 1, 4])  # [3, N, H, L, Dh]
        q = layers.reshape(layers.slice(qkv, axes=[0], starts=[0], ends=[1]),
                           [n_rows, HEADS, L, D // HEADS])
        k = layers.reshape(layers.slice(qkv, axes=[0], starts=[1], ends=[2]),
                           [n_rows, HEADS, L, D // HEADS])
        v = layers.reshape(layers.slice(qkv, axes=[0], starts=[2], ends=[3]),
                           [n_rows, HEADS, L, D // HEADS])
        ctx = layers.fused_attention(q, k, v, causal=True, segment_ids=seg)
        ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                             [n_rows, L, D])
        logits = layers.fc(ctx, size=VOCAB, num_flatten_dims=2)
        loss_tok = layers.softmax_with_cross_entropy(
            logits, layers.unsqueeze(labels, axes=[2]))
        # the mask derives from integer data, so no gradient flows
        # through it (nothing to stop-gradient)
        mask = layers.cast(layers.unsqueeze(seg, axes=[2]) > 0, "float32")
        denom = layers.reduce_sum(mask)
        loss = layers.reduce_sum(loss_tok * mask) / denom
        fluid.optimizer.Adam(3e-3).minimize(loss)
    return main, startup, loss


def main():
    rng = np.random.RandomState(0)
    # synthetic "language": token t is always followed by (t + 1) % VOCAB
    seqs = []
    for _ in range(24):
        n = rng.randint(3, 15)
        start = rng.randint(0, VOCAB)
        seqs.append((start + np.arange(n)) % VOCAB)
    tokens, seg, pos = pack_sequences(seqs, L)
    n_rows = tokens.shape[0]
    print("packed %d ragged sequences into %d rows of %d (fill %.0f%%)"
          % (len(seqs), n_rows, L, 100.0 * (seg > 0).mean()))
    assert n_rows < len(seqs)

    # next-token labels WITHIN each segment; boundaries get masked later
    labels = np.roll(tokens, -1, axis=1)
    label_valid = (seg > 0) & (seg == np.roll(seg, -1, axis=1))
    seg_for_loss = np.where(label_valid, seg, 0).astype("int32")

    main_p, startup, loss = build(n_rows)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"tokens": tokens, "seg": seg_for_loss,
            "pos": pos.astype("int64"), "labels": labels}
    losses = []
    for step in range(60):
        (lv,) = exe.run(main_p, feed=feed, fetch_list=[loss])
        losses.append(float(np.ravel(lv)[0]))
        if step % 20 == 0:
            print("step %d  masked loss %.4f" % (step, losses[-1]))
    print("final loss %.4f (from %.4f)" % (losses[-1], losses[0]))
    assert losses[-1] < 0.5 * losses[0], (losses[0], losses[-1])
    print("ok: the packed LM learned the successor rule")


if __name__ == "__main__":
    main()
