"""Adapter: GPT-2 decoder-only causal LM (Radford et al. 2019) trained
through paddle_tpu.models.gpt2.gpt2_lm_program.  See transformer_wmt.py
for what an adapter is.
"""

import numpy as np

# |program loss - reference loss| on the sampled rows: bf16 AMP matmuls
# against float32 "highest".  On the chip at full width the difference was
# at most 6.9e-5 in 13 runs on one chip and 1.0e-4 in 7 runs on the dp2 x
# mp2 mesh, each run another seed (my chip runs, PR 22), on a loss of ~7.4
# after the window: the tolerance is 20 times that.  A missing causal mask,
# position table or final layer norm moves the loss by 1e-1 or more.
TOLERANCE = 2e-3


def _hp(model, dropout=None):
    from paddle_tpu.models import gpt2

    class HP(gpt2.GPT2Config):
        pass

    for k, v in model.items():
        setattr(HP, k, v)
    if dropout is not None:
        HP.dropout = dropout
    return HP


def build(cfg, work, mesh=None, forward_only=False):
    from paddle_tpu.models import gpt2

    train = cfg["train"]
    hp = _hp(cfg["model"], dropout=0.0 if forward_only else None)
    main, startup, feeds, fetches = gpt2.gpt2_lm_program(
        hp, seq_len=int(work["seq_len"]), lr=float(train["learning_rate"]),
        is_test=forward_only, use_bf16=bool(train["use_bf16"]), mesh=mesh)
    return {"main": main, "startup": startup, "feeds": feeds,
            "loss": fetches[0]}


def make_batch(cfg, work, seed):
    """Full-length sequences of random tokens with p(k) ~ 1/k (log-uniform,
    as word frequencies are: the unigram distribution is learnt within tens
    of steps, so `correct` can ask for a falling loss without reading
    noise); labels are the ids shifted by one; every position counts."""
    b, t = int(work["batch"]), int(work["seq_len"])
    vocab = cfg["model"]["vocab_size"]
    rng = np.random.default_rng(seed)
    ids = np.floor(np.exp(rng.uniform(0.0, np.log(vocab), (b, t + 1)))).astype(
        "int64").clip(1, vocab - 1)
    return {"ids": ids[:, :-1], "labels": ids[:, 1:],
            "loss_weight": np.ones((b, t), "float32")}


def work_units(batch):
    """Target tokens that count towards the loss."""
    return float(batch["loss_weight"].sum())


def model_flops(cfg, work):
    """Matmul operations of forward + backward (3 x forward) from the
    shapes alone: full T x T attention, the vocabulary head included,
    recomputation never counted."""
    m = cfg["model"]
    b, t = int(work["batch"]), int(work["seq_len"])
    d, n, v = m["d_model"], m["n_layer"], m["vocab_size"]
    rows = b * t
    layer = (4 * 2.0 * rows * d * d          # q, k, v, o
             + 2.0 * 2.0 * b * t * t * d      # QK^T and PV
             + 2 * 2.0 * rows * d * 4 * d)    # MLP in and out
    return 3.0 * (n * layer + 2.0 * rows * d * v)


# --------------------------------------------------------------------------
# plain reference: pre-LN decoder, gelu(erf) MLP, learned positions
# --------------------------------------------------------------------------
def reference_loss(cfg, params, batch):
    import jax
    import jax.numpy as jnp

    weights = [jnp.asarray(v, jnp.float32) for _, v in params]
    with jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda w, b: _forward(cfg["model"], w, b))(
            weights, batch))


def _forward(m, weights, batch):
    import jax
    import jax.numpy as jnp

    d, h, v = m["d_model"], m["n_head"], m["vocab_size"]
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

    def attention(x):
        wq, wk, wv, wo = take(d, d), take(d, d), take(d, d), take(d, d)
        bsz, t, _ = x.shape

        def heads(y):
            return y.reshape(bsz, t, h, d // h).transpose(0, 2, 1, 3)

        q, k, val = heads(x @ wq), heads(x @ wk), heads(x @ wv)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d // h) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), val)
        return ctx.transpose(0, 2, 1, 3).reshape(bsz, t, d) @ wo

    def mlp(x):
        w1, b1, w2, b2 = take(d, 4 * d), take(4 * d), take(4 * d, d), take(d)
        return jax.nn.gelu(x @ w1 + b1, approximate=False) @ w2 + b2

    ids = jnp.asarray(batch["ids"])
    emb, pos = take(v, d), take(m["n_ctx"], d)
    x = emb[ids] + pos[: ids.shape[1]][None]
    for _ in range(m["n_layer"]):
        x = x + attention(layer_norm(x))
        x = x + mlp(layer_norm(x))
    x = layer_norm(x)
    logits = x @ (emb.T if m.get("tie_embeddings") else take(d, v))
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"])
    return ((lse - picked) * w).sum() / w.sum()
