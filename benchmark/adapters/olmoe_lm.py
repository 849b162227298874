"""Adapter: OLMoE (Muennighoff et al. 2024, arXiv:2409.02060; model type
`olmoe`) trained through paddle_tpu.models.olmoe.olmoe_lm_program.  See
transformer_wmt.py for what an adapter is.  The configuration file keeps
the widths under the keys of the published config.json, at its top level;
`model` holds what that file does not fix (the paper's loss weights).
"""

import numpy as np

# |program loss - reference loss| on the sampled row (4,096 positions),
# after the window.  Two things differ: bf16 AMP matmuls against float32
# "highest", and the experts a token is sent to, because attention ran in
# bf16 upstream of a float32 router and a top-8 of 64 is discontinuous (a
# flipped choice trades the 8th expert for the 9th, whose router weights
# are nearly equal).  On the chip at full width the difference was at most
# 5.4e-4 in 15 runs of nine seeds (2.2e-5 .. 5.4e-4; my chip runs, PR 25)
# on a loss of 3.4 .. 4.2: the tolerance is 11 times that.  A loss without
# its z term or its load-balance term is off by 8e-2 or more and fails (z
# term dropped: 8.45e-2, measured).  A router fed bf16 rows does NOT fail
# it (2.2e-4, measured: inside the spread above): the router's precision
# is pinned by tests/test_moe_ffn_op.py on the CPU (PERF.md section 7).
TOLERANCE = 6e-3

_HP_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "num_experts", "num_experts_per_tok",
            "norm_topk_prob", "rms_norm_eps", "rope_theta",
            "max_position_embeddings")


def _arch(cfg):
    """The numbers the architecture is made of, from both places."""
    return dict({k: cfg[k] for k in _HP_KEYS}, **cfg["model"])


def build(cfg, work, mesh=None, forward_only=False):
    from paddle_tpu.models import olmoe

    class HP(olmoe.OLMoEConfig):
        pass

    for k, v in _arch(cfg).items():
        setattr(HP, k, v)
    train = cfg["train"]
    main, startup, feeds, fetches = olmoe.olmoe_lm_program(
        HP, seq_len=int(work["seq_len"]), lr=float(train["learning_rate"]),
        is_test=forward_only, use_bf16=bool(train["use_bf16"]), mesh=mesh)
    return {"main": main, "startup": startup, "feeds": feeds,
            "loss": fetches[0]}


def make_batch(cfg, work, seed):
    """Full-length packed sequences of random tokens with p(k) ~ 1/k, as
    gpt2_lm makes them; labels are the ids shifted by one; every position
    counts."""
    b, t = int(work["batch"]), int(work["seq_len"])
    vocab = cfg["vocab_size"]
    rng = np.random.default_rng(seed)
    ids = np.floor(np.exp(rng.uniform(0.0, np.log(vocab), (b, t + 1)))).astype(
        "int64").clip(1, vocab - 1)
    return {"ids": ids[:, :-1], "labels": ids[:, 1:],
            "loss_weight": np.ones((b, t), "float32")}


def work_units(batch):
    """Target tokens that count towards the loss."""
    return float(batch["loss_weight"].sum())


def forward_flops(cfg, work):
    """Matmul operations of one forward pass by part, over the ACTIVE
    parameters: each token runs its top-k experts, not all of them."""
    b, t = int(work["batch"]), int(work["seq_len"])
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    rows, n = b * t, cfg["num_hidden_layers"]
    return {
        "attention": n * (4 * 2.0 * rows * d * d        # q, k, v, o
                          + 2 * 2.0 * b * t * t * d),   # QK^T and PV, T x T
        "router": n * 2.0 * rows * d * cfg["num_experts"],
        "experts": n * expert_matmul_cost(cfg, work)["flops_forward"],
        "head": 2.0 * rows * d * v,
    }


def model_flops(cfg, work):
    """Forward + backward (3 x forward), recomputation never counted."""
    return 3.0 * sum(forward_flops(cfg, work).values())


def expert_matmul_cost(cfg, work):
    """What one layer's two grouped matmuls must do in a step, from the
    shapes: 6 N k d f operations forward (N k rows through [d, 2f] and
    [f, d]) and twice that backward; bytes with every expert's weights read
    once per matmul (and their gradient written once), and the rows of
    each matmul's operands and result read or written once, in bf16."""
    rows = (int(work["batch"]) * int(work["seq_len"])
            * cfg["num_experts_per_tok"])
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    fwd = 6.0 * rows * d * f
    weights = 2.0 * e * 3 * d * f
    row_bytes = 2.0 * rows * ((d + 2 * f) + (f + d))
    # backward: the rows' gradient reads the weights and both row arrays
    # again; the weights' gradient reads the rows and writes [E, ., .]
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * (weights + row_bytes)}


# --------------------------------------------------------------------------
# plain reference (this file's own copy of paddle_tpu/models/
# olmoe_reference.py's equations; benchmark/tests holds the two together):
# float32, "highest", experts as a loop over a boolean mask, full [T, T]
# softmax under a tril mask, rotate-half RoPE over the whole head.  The
# paper's loss weights, not config.json's; no document mask in a packed
# sequence.
# --------------------------------------------------------------------------
def reference_loss(cfg, params, batch):
    """On the host's CPU device where jax has one: on the chip the
    reference would have to fit beside 10 GB of training state."""
    import jax
    import jax.numpy as jnp

    try:
        device = jax.devices("cpu")[0]
    except RuntimeError:  # the process was given the accelerator alone
        device = None

    def place(v, dtype=None):
        if device is None:
            return jnp.asarray(v, dtype)  # no second copy on the chip
        return jax.device_put(np.asarray(v, dtype), device)

    weights = [place(v, jnp.float32) for _, v in params]
    batch = {k: place(v) for k, v in batch.items()}
    arch = _arch(cfg)
    with jax.default_device(device), \
            jax.default_matmul_precision("highest"):
        return float(jax.jit(lambda w, b: _loss(arch, w, b))(weights, batch))


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None]
    ang = jnp.concatenate([ang, ang], -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _loss(m, weights, batch):
    import jax
    import jax.numpy as jnp

    d, h, v = m["hidden_size"], m["num_attention_heads"], m["vocab_size"]
    f, n_experts = m["intermediate_size"], m["num_experts"]
    k, eps, theta = m["num_experts_per_tok"], m["rms_norm_eps"], m["rope_theta"]
    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def attention(x):
        wq, wk, wv = take(d, d), take(d, d), take(d, d)
        q_norm, k_norm, wo = take(d), take(d), take(d, d)
        bsz, t, _ = x.shape

        def heads(y):
            return y.reshape(bsz, t, h, d // h).transpose(0, 2, 1, 3)

        q = _rope(heads(_rms_norm(x @ wq, q_norm, eps)), theta)
        key = _rope(heads(_rms_norm(x @ wk, k_norm, eps)), theta)
        val = heads(x @ wv)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, key) * (d // h) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), val)
        return ctx.transpose(0, 2, 1, 3).reshape(bsz, t, d) @ wo

    def moe(x):
        router = take(d, n_experts)
        gate_up, down = take(n_experts, d, 2 * f), take(n_experts, f, d)
        x2 = x.reshape(-1, d)
        logits = x2 @ router
        probs = jax.nn.softmax(logits, -1)
        top_p, top_e = jax.lax.top_k(probs, k)
        if m.get("norm_topk_prob"):
            top_p = top_p / top_p.sum(-1, keepdims=True)
        y = jnp.zeros_like(x2)
        for e in range(n_experts):
            chosen = top_e == e
            weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
            gu = x2 @ gate_up[e]
            out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down[e]
            y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out,
                              0.0)
        frac = (top_e[..., None] == jnp.arange(n_experts)).sum((0, 1)) \
            / x2.shape[0]
        lb = n_experts * jnp.sum(frac * probs.mean(0))
        z = jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)
        return y.reshape(x.shape), lb, z

    x = take(v, d)[jnp.asarray(batch["ids"])]
    router_loss = 0.0
    for _ in range(m["num_hidden_layers"]):
        x = x + attention(_rms_norm(x, take(d), eps))
        y, lb, z = moe(_rms_norm(x, take(d), eps))
        x = x + y
        router_loss = (router_loss + m["router_aux_loss_coef"] * lb
                       + m["router_z_loss_coef"] * z)
    logits = _rms_norm(x, take(d), eps) @ take(d, v)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"])
    return ((lse - picked) * w).sum() / w.sum() + router_loss
