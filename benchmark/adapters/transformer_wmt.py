"""Adapter: encoder-decoder Transformer (Vaswani et al. 2017) trained
through paddle_tpu.models.transformer.wmt_transformer_program.

An adapter is what the harness needs from one architecture, and nothing
of any cell: build the program through the repo's public builder, make a
batch from a seed, count work units and the operations the maths requires,
and compute the loss with a plain float32 reference.
"""

import numpy as np

# |program loss - reference loss| on the sampled rows.  The program runs
# bf16 matmuls under the AMP pass with f32 accumulation and an f32 loss;
# the reference is float32 at "highest" precision.  On the chip at full
# width the difference was at most 1.7e-4 in 13 runs of as many seeds (my
# chip runs, PR 22) on a loss of ~7.2 after the window: the tolerance is 12
# times that.  A dropped mask, a missing sqrt(d) scale or a lost
# label-smoothing term moves the loss by 1e-1 or more.
TOLERANCE = 2e-3

NEG_BIAS = -1e9


def _hp(model, dropout=None):
    from paddle_tpu.models import transformer as tfm

    class HP(tfm.ModelHyperParams):
        fused_attn = True

    for k, v in model.items():
        setattr(HP, k, v)
    if dropout is not None:
        HP.dropout = dropout
    return HP


def build(cfg, work, mesh=None, forward_only=False):
    """The train program, or (forward_only) the same architecture without
    dropout or optimizer, sharing the weights by name through the scope."""
    from paddle_tpu.models import transformer as tfm

    train = cfg["train"]
    hp = _hp(cfg["model"], dropout=0.0 if forward_only else None)
    main, startup, feeds, fetches = tfm.wmt_transformer_program(
        hp, src_len=int(work["src_len"]), trg_len=int(work["trg_len"]),
        learning_rate=float(train["learning_rate"]),
        warmup_steps=int(train["warmup_steps"]), is_test=forward_only,
        use_bf16=bool(train["use_bf16"]), mesh=mesh)
    return {"main": main, "startup": startup, "feeds": feeds,
            "loss": fetches[0]}


def zipf_ids(rng, vocab, shape):
    """Token ids in [1, vocab) with p(k) ~ 1/k (log-uniform), as word
    frequencies are.  Uniform ids leave nothing to learn but the ring
    itself; with these the unigram distribution is learnt within tens of
    steps, so `correct` can ask for a falling loss without reading noise."""
    return np.floor(np.exp(rng.uniform(0.0, np.log(vocab), shape))).astype(
        "int64").clip(1, vocab - 1)


def make_batch(cfg, work, seed):
    """Padded batch with its masks, lengths spread evenly over [len/2,
    len] — the feed contract of the builder (dense bias tensors included: a Fluid
    user feeds them whether or not the fused path reads them)."""
    m = cfg["model"]
    b, s, t = int(work["batch"]), int(work["src_len"]), int(work["trg_len"])
    rng = np.random.default_rng(seed)
    src = zipf_ids(rng, m["src_vocab_size"], (b, s))
    trg = zipf_ids(rng, m["trg_vocab_size"], (b, t))
    lbl = zipf_ids(rng, m["trg_vocab_size"], (b, t))
    # every length from len/2 to len equally often, in seeded order: the
    # batch's non-pad tokens are the same number for every seed, so that
    # tokens per second does not move with the draw
    src_lens = rng.permutation(np.linspace(s // 2, s, b).round().astype(int))
    trg_lens = rng.permutation(np.linspace(t // 2, t, b).round().astype(int))
    src_pad = np.arange(s)[None, :] >= src_lens[:, None]
    trg_pad = np.arange(t)[None, :] >= trg_lens[:, None]
    src_bias = np.where(src_pad, NEG_BIAS, 0.0).astype(
        "float32")[:, None, None, :]
    causal = np.triu(np.ones((t, t), "float32"), k=1) * NEG_BIAS
    trg_bias = (np.where(trg_pad[:, None, :], NEG_BIAS, 0.0)
                + causal[None]).astype("float32")[:, None, :, :]
    return {
        "src_word": src, "trg_word": trg, "lbl_word": lbl,
        "src_slf_attn_bias": src_bias, "trg_slf_attn_bias": trg_bias,
        "trg_src_attn_bias": src_bias.copy(),
        "lbl_weight": (~trg_pad).astype("float32"),
    }


def work_units(batch):
    """Non-pad target tokens."""
    return float(batch["lbl_weight"].sum())


def model_flops(cfg, work):
    """Operations one train step requires: matmuls of the forward pass
    from the shapes alone, padded positions included, full T x T
    attention, times 3 for forward + backward; recomputation never
    counted."""
    m = cfg["model"]
    b, s, t = int(work["batch"]), int(work["src_len"]), int(work["trg_len"])
    d, f, n, v = m["d_model"], m["d_inner_hid"], m["n_layer"], \
        m["trg_vocab_size"]

    def proj(rows, k, n_out):
        return 2.0 * rows * k * n_out

    def attn(tq, tk):  # QK^T and PV over all heads
        return 2.0 * 2.0 * b * tq * tk * d

    enc = 4 * proj(b * s, d, d) + attn(s, s) + 2 * proj(b * s, d, f)
    dec = (4 * proj(b * t, d, d) + attn(t, t)
           + 2 * proj(b * t, d, d) + 2 * proj(b * s, d, d) + attn(t, s)
           + 2 * proj(b * t, d, f))
    head = proj(b * t, d, v)
    return 3.0 * (n * enc + n * dec + head)


# --------------------------------------------------------------------------
# plain reference: post-LN encoder-decoder, float32, no dropout
# --------------------------------------------------------------------------
def reference_loss(cfg, params, batch):
    """Label-smoothed, weight-averaged cross entropy of the architecture's
    forward pass in plain jax.numpy.  `params` are (name, array) in the
    order the architecture creates them; masks are the dense bias feeds."""
    import jax
    import jax.numpy as jnp

    m = cfg["model"]
    d, h, n_layer = m["d_model"], m["n_head"], m["n_layer"]
    eps = float(m.get("label_smooth_eps", 0.1))
    weights = [jnp.asarray(v, jnp.float32) for _, v in params]
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda w, b: _forward(m, d, h, n_layer, eps,
                                                   w, b))(weights, batch))


def _forward(m, d, h, n_layer, eps, weights, batch):
    import jax
    import jax.numpy as jnp

    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def layer_norm(x):
        g, b = take(d), take(d)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def mha(q_in, kv_in, bias):
        wq, wk, wv, wo = take(d, d), take(d, d), take(d, d), take(d, d)

        def heads(x):
            bsz, t, _ = x.shape
            return x.reshape(bsz, t, h, d // h).transpose(0, 2, 1, 3)

        q, k, v = heads(q_in @ wq), heads(kv_in @ wk), heads(kv_in @ wv)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d // h) ** -0.5 + bias
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        bsz, _, t, _ = ctx.shape
        return ctx.transpose(0, 2, 1, 3).reshape(bsz, t, d) @ wo

    def ffn(x):
        f = m["d_inner_hid"]
        w1, b1, w2, b2 = take(d, f), take(f), take(f, d), take(d)
        return jax.nn.relu(x @ w1 + b1) @ w2 + b2

    def embed(ids, vocab):
        emb, pos = take(vocab, d), take(m["max_length"], d)
        return emb[ids] * d ** 0.5 + pos[: ids.shape[1]][None]

    src_bias = jnp.asarray(batch["src_slf_attn_bias"])
    trg_bias = jnp.asarray(batch["trg_slf_attn_bias"])
    cross_bias = jnp.asarray(batch["trg_src_attn_bias"])

    x = embed(jnp.asarray(batch["src_word"]), m["src_vocab_size"])
    for _ in range(n_layer):
        x = layer_norm(x + mha(x, x, src_bias))
        x = layer_norm(x + ffn(x))
    y = embed(jnp.asarray(batch["trg_word"]), m["trg_vocab_size"])
    for _ in range(n_layer):
        y = layer_norm(y + mha(y, y, trg_bias))
        y = layer_norm(y + mha(y, x, cross_bias))
        y = layer_norm(y + ffn(y))
    logits = y @ take(d, m["trg_vocab_size"])
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["lbl_word"])[..., None], -1)[..., 0]
    tok = (1.0 - eps) * (lse - picked) + eps * (lse - logits.mean(-1))
    w = jnp.asarray(batch["lbl_weight"])
    return (tok * w).sum() / w.sum()
