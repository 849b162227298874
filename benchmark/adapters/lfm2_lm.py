"""Adapter: LFM2-MoE (LiquidAI LFM2-8B-A1B; model type `lfm2_moe`) trained
through paddle_tpu.models.lfm2.lfm2_lm_program.  See transformer_wmt.py for
what an adapter is.  The configuration file keeps the widths under the
keys of the published config.json, at its top level; `num_experts` there
counts the experts this chip HOLDS of each layer (model-configs guide,
section 4), `share` says over how many the router chooses and where the
held range starts.

Every closed form here counts the held experts' EXPECTED rows, N k E_held /
E (even routing), and `train_mfu` is over that count whatever a step had.
The rows a step really had are no function of the shapes: they are the
router's decisions, a program counter (`moe_rows_held_share`,
readers/moe_held_stat.py, which logs them by layer and over the
expectation).  The training program's `expert_bias_update` keeps them at
0.9 .. 1.2 x the expectation in four seeds of five (0.5 .. 1.06 x a layer
in a seed whose training stalls; PERF.md section 6); before it they were
0.45 .. 1.6 x and the driver refused the cell for the noise.
"""

import numpy as np

# |program loss - reference loss| on the sampled row (8,192 positions),
# after the window, at whatever loss the seed has reached by then (0.09 ..
# 1.4 in 16 of 19 runs: the ring of 8 Zipf batches is memorised at Adam
# 4e-4; 6.7 .. 7.0 in three whose training stalled).
# Two things differ: bf16 AMP matmuls against float32 "highest", and the
# experts a token is sent to, because the layers before a float32 router
# ran in bf16 and a top-4 of 32 is discontinuous.  On the chip at full
# width the difference was 7.6e-6 .. 2.8e-4 in 19 runs of 19 seeds at these
# settings (1.9e-5 .. 6.0e-4 in 28 runs before the bias was balanced, and
# up to 1.7e-3 under a slower Adam, at a loss of 2.8 .. 7.5; my chip runs,
# PR 30): it grows with the loss.  The reference with one departure, on
# the same weights, 2 seeds: expert_bias dropped from the selection 2.4e-2
# / 3.5e-2, the top-k renormalisation left out 0.096 / 0.073, the
# convolution reading one step ahead 8.7 / 8.1: each fails.  The tolerance
# lies between the two readings.  NOT caught by this one float: a router
# fed bf16 rows (within 9e-5 of the exact reference), the 1e-6 alone
# (under 2e-6), QK-norm over the whole projection instead of per head
# (within 6.1e-4: the two differ by a head's rms over the projection's,
# which no weight changes): tests/test_moe_ffn_op.py and
# tests/test_lfm2_model.py pin those on the CPU in float32 (PERF.md
# section 4).
TOLERANCE = 2e-3

_HP_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers", "layer_types",
            "num_dense_layers", "num_attention_heads", "num_key_value_heads",
            "num_experts_per_tok", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "norm_eps", "rope_theta", "conv_L_cache",
            "max_position_embeddings")


def _arch(cfg):
    """The numbers the architecture is made of, under the builder's names:
    the router's width is `num_experts`, the file's count of held experts
    `num_local_experts`."""
    arch = {k: cfg[k] for k in _HP_KEYS}
    arch["layer_types"] = list(arch["layer_types"])
    arch["num_experts"] = int(cfg["share"]["router_experts"])
    arch["num_local_experts"] = int(cfg["num_experts"])
    arch["expert_offset"] = int(cfg["share"]["expert_offset"])
    return arch


def build(cfg, work, mesh=None, forward_only=False):
    from paddle_tpu.models import lfm2

    class HP(lfm2.LFM2MoEConfig):
        pass

    for k, v in _arch(cfg).items():
        setattr(HP, k, v)
    train = cfg["train"]
    main, startup, feeds, fetches = lfm2.lfm2_lm_program(
        HP, seq_len=int(work["seq_len"]), lr=float(train["learning_rate"]),
        is_test=forward_only, use_bf16=bool(train["use_bf16"]), mesh=mesh)
    return {"main": main, "startup": startup, "feeds": feeds,
            "loss": fetches[0]}


def make_batch(cfg, work, seed):
    """Full-length packed sequences of random tokens with p(k) ~ 1/k over
    the vocabulary slice, as gpt2_lm and olmoe_lm make them; labels are the
    ids shifted by one; every position counts."""
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


def _layers(cfg):
    """(conv layers, attention layers, dense layers, expert layers)."""
    kinds = list(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    return (kinds.count("conv"), kinds.count("full_attention"), dense,
            len(kinds) - dense)


def _held_rows(cfg, work):
    """Rows one expert layer's held experts expect in a step: N k E_held /
    E, every expert equally likely."""
    return (int(work["batch"]) * int(work["seq_len"])
            * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / float(cfg["share"]["router_experts"]))


def forward_flops(cfg, work):
    """Operations of one forward pass by part.  Matmuls, plus the gated
    convolution's elementwise work (short_conv_cost's count).  The experts
    are counted over the rows this chip's share of them expects, not over
    all N k routed rows: the others run on chips that are not here."""
    b, t = int(work["batch"]), int(work["seq_len"])
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    dh = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * dh
    rows = b * t
    n_conv, n_attn, n_dense, n_moe = _layers(cfg)
    return {
        "short_conv_projections": n_conv * 2.0 * rows * d * (3 * d + d),
        "short_conv": n_conv * short_conv_cost(cfg, work)["flops_forward"],
        "attention": n_attn * (2.0 * rows * d * (2 * d + 2 * kv)  # q o, k v
                               + 2 * 2.0 * b * t * t * d),  # QK^T, PV: T x T
        "dense_mlp": n_dense * 3 * 2.0 * rows * d * cfg["intermediate_size"],
        "router": n_moe * 2.0 * rows * d * cfg["share"]["router_experts"],
        "experts": n_moe * expert_matmul_cost(cfg, work)["flops_forward"],
        "head": 2.0 * rows * d * v,
    }


def model_flops(cfg, work):
    """Forward + backward (3 x forward), recomputation never counted."""
    return 3.0 * sum(forward_flops(cfg, work).values())


def expert_matmul_cost(cfg, work):
    """What one layer's two grouped matmuls must do in a step, from the
    shapes, over the rows the held experts EXPECT (N k E_held / E; the dead
    part of the static row buffer is no work) and the held experts'
    weights: 6 rows d f operations forward (through [d, 2f] and [f, d])
    and twice that backward; bytes with every held expert's weights read
    once per matmul (and their gradient written once), and the rows of
    each matmul's operands and result read or written once, in bf16.  A
    step's real rows are 0.8 .. 1.2 x these over the layers (see the top
    of this file; readers/moe_held_stat.py logs the ratio), and
    `expert_matmul_roofline` is off by that ratio (44.6 and 44.8 % at 1.02
    and 0.78 x: far from 100 either way)."""
    rows = _held_rows(cfg, work)
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    fwd = 6.0 * rows * d * f
    weights = 2.0 * e * 3 * d * f
    row_bytes = 2.0 * rows * ((d + 2 * f) + (f + d))
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * (weights + row_bytes)}


def short_conv_cost(cfg, work):
    """What one short_conv op must do in a step: forward it reads BCX
    [N, 3d] and writes the result [N, d] once, backward it reads BCX and
    the result's gradient and writes BCX's gradient, all in bf16 (the
    [d, L] filter and its gradient are nothing beside them); 2 L + 2
    operations a value forward (B * u, L multiply-adds, the C gate) and
    twice that backward."""
    rows = int(work["batch"]) * int(work["seq_len"])
    d, taps = cfg["hidden_size"], cfg["conv_L_cache"]
    fwd = (2.0 * taps + 2.0) * rows * d
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 2.0 * rows * d * ((3 + 1) + (3 + 1 + 3))}


# --------------------------------------------------------------------------
# plain reference (this file's own copy of paddle_tpu/models/
# lfm2_reference.py's equations; benchmark/tests holds the two together):
# float32, "highest", the convolution as L shifted adds, the held experts
# as a loop over a boolean mask (what the absent ones would add is left
# out, as in the program), full [T, T] softmax under a tril mask, computed
# one key/value head with its query heads at a time so that 32 heads of
# [8192, 8192] scores need not exist at once, rotate-half RoPE over the
# whole head, the head tied to the embedding.  No auxiliary loss; no
# document mask in a packed sequence.
# --------------------------------------------------------------------------
def reference_loss(cfg, params, batch):
    """On the host's CPU device where jax has one: on the chip the
    reference would have to fit beside 8 GB of training state."""
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
    """x [..., T, Dh]."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    ang = jnp.concatenate([ang, ang], -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def _loss(m, weights, batch):
    import jax
    import jax.numpy as jnp

    d, h, kv = (m["hidden_size"], m["num_attention_heads"],
                m["num_key_value_heads"])
    dh, eps, theta = d // h, m["norm_eps"], float(m["rope_theta"])
    k, taps = m["num_experts_per_tok"], m["conv_L_cache"]
    held, offset = m["num_local_experts"], m["expert_offset"]
    f_moe = m["moe_intermediate_size"]
    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def short_conv(x):
        w_in, filt, w_out = take(d, 3 * d), take(d, taps), take(d, d)
        t = x.shape[1]
        bcx = x @ w_in
        bu = bcx[..., :d] * bcx[..., 2 * d:]
        v = jnp.zeros_like(bu)
        for j in range(taps):
            back = taps - 1 - j
            v = v + filt[:, j] * jnp.concatenate(
                [jnp.zeros_like(bu[:, :back]), bu[:, :t - back]], 1)
        return (bcx[..., d:2 * d] * v) @ w_out

    def attention(x):
        wq, wk, wv = take(d, d), take(d, kv * dh), take(d, kv * dh)
        q_norm, k_norm, wo = take(dh), take(dh), take(d, d)
        bsz, t, _ = x.shape
        g = h // kv
        # [kv, B, g or 1, T, dh]: one key/value head and the g query heads
        # it serves at a time
        q = _rope(_rms_norm((x @ wq).reshape(bsz, t, kv, g, dh), q_norm,
                            eps).transpose(2, 0, 3, 1, 4), theta)
        key = _rope(_rms_norm((x @ wk).reshape(bsz, t, kv, 1, dh), k_norm,
                              eps).transpose(2, 0, 3, 1, 4), theta)
        val = (x @ wv).reshape(bsz, t, kv, 1, dh).transpose(2, 0, 3, 1, 4)
        causal = jnp.tril(jnp.ones((t, t), bool))

        def group(qkv):
            qg, kg, vg = qkv
            s = jnp.einsum("bgqd,bxkd->bgqk", qg, kg) * dh ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            return jnp.einsum("bgqk,bxkd->bgqd", jax.nn.softmax(s, -1), vg)

        ctx = jax.lax.map(group, (q, key, val))  # [kv, B, g, T, dh]
        return ctx.transpose(1, 3, 0, 2, 4).reshape(bsz, t, d) @ wo

    def dense_mlp(x):
        f = m["intermediate_size"]
        w1, w3, w2 = take(d, f), take(d, f), take(f, d)
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def moe(x):
        router = take(d, m["num_experts"])
        bias = take(m["num_experts"]) if m["use_expert_bias"] else 0.0
        gate_up, down = take(held, d, 2 * f_moe), take(held, f_moe, d)
        x2 = x.reshape(-1, d)
        s = jax.nn.sigmoid(x2 @ router)
        _, top_e = jax.lax.top_k(s + bias, k)
        top_p = jnp.take_along_axis(s, top_e, -1)
        if m["norm_topk_prob"]:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-6)
        y = jnp.zeros_like(x2)
        for local in range(held):
            chosen = top_e == offset + local
            weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
            gu = x2 @ gate_up[local]
            out = (jax.nn.silu(gu[:, :f_moe]) * gu[:, f_moe:]) @ down[local]
            y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out,
                              0.0)
        return y.reshape(x.shape)

    emb = take(m["vocab_size"], d)
    x = emb[jnp.asarray(batch["ids"])]
    for i, kind in enumerate(m["layer_types"]):
        hidden = _rms_norm(x, take(d), eps)
        x = x + (short_conv(hidden) if kind == "conv" else attention(hidden))
        hidden = _rms_norm(x, take(d), eps)
        x = x + (dense_mlp(hidden) if i < m["num_dense_layers"]
                 else moe(hidden))
    logits = _rms_norm(x, take(d), eps) @ emb.T
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"])
    return ((lse - picked) * w).sum() / w.sum()
