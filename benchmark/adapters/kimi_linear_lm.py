"""Adapter: Kimi-Linear-48B-A3B (moonshotai; model type `kimi_linear`)
trained through paddle_tpu.models.kimi_linear.kimi_linear_lm_program.  See
transformer_wmt.py for what an adapter is.  The configuration file keeps
the widths under the keys of the published config.json, at its top level;
`num_experts` there counts the experts this chip HOLDS of each layer
(model-configs guide, section 4), `share` says over how many the router
chooses and where the held range starts; `train` carries the balancing
step's `rate` and `max_step` beside the learning rate.

`model_flops` counts a Kimi Delta Attention core by `kda_core_cost`'s
forward operations (the chunkwise form at C = 64: 2 C (3 dk + 2 dv) +
6 dk dv a token a head, the triangular solve's C^3 left out: the same
work whatever implements the op), and the one latent-attention core over
the causal half, T^2 / 2 pairs a head, the convention
kanana2_lm.forward_flops uses.  Like lfm2_lm, kanana2_lm and trinity_lm it
counts the held experts' EXPECTED rows, N k E_held / E (even routing),
whatever a step had; `moe_rows_held_share` (readers/moe_held_stat.py) is
the counter that says what it had.
"""

import numpy as np

# What decides `correct` here, on the sampled row after the window:
# kanana2_lm's and trinity_lm's comparison, a PAIRED reading under LIMITS
# and the harness's own |program loss - reference loss| <= TOLERANCE.  The
# forward-only program leaves every token's cost in the scope
# (`kimi_linear.EVAL_ROWS`); `cost_rms` is the root mean square of its
# differences from the reference's rows, and `cost_rms_over_bf16` is that
# in units of what the all-bfloat16 reference's rows differ by from the
# exact float32 one's ON THE SAME WEIGHTS (`bf16_unit`: one more reference
# a comparison).  `reference_loss` answers NaN, which no tolerance admits,
# where the reading is over its limit.  Why a paired reading: a mean over
# thousands of tokens averages bf16 rounding away, so the loss alone cannot
# tell the stated precision (bf16 AMP matmuls and bf16 operands of the KDA
# core's products; f32 masters, router, norm statistics, log-decay, beta,
# running sums, triangular solve, carried state, softmax and cross-entropy)
# from the one below it.  Readings on the chip at full width (my chip
# runs, PR 45: 12 runs of the cell on 6 seeds, 5 traced runs of 2 more, and
# tools/kanana2_departures.py --cell kimi_linear_48b_a3b_train, which makes
# this comparison on the same weights, on seeds 2200000021 and 2210000033
# at 120 and 132 steps; PERF.md section 4 has the table):
#
#   cost_rms_over_bf16   the program against the exact reference 0.155,
#              0.161, 0.297, 0.332 in the tool's 4 states and 0.113 .. 0.240
#              in the cell's 6 seeds (absolute 2.1e-2 .. 2.5e-2 in all: the
#              UNIT moves with the seed, 0.074 .. 0.18); the whole reference
#              in bfloat16 0.995, 0.999, 0.997, 1.010.  ISSUE 45 said to
#              start at trinity_lm's 0.75 and set the limit from the two
#              readings: 0.6, 1.81 x over the largest sound reading and
#              1.66 x under the smallest all-bfloat16 one (the 12 runs of
#              the cell ran under 0.45, which every one of them meets as
#              well).  Wrong models at 120 steps of seed 2200000021, in
#              units: the decay a head's mean 14.3, left out 18.3, beta
#              left out 6.6, the k k^T correction left out 9.3, q and k not
#              normalised 18.1, the convolution one step ahead 17.0, the
#              output gate left out 2.1, routed_scaling_factor left out
#              0.65 (and 5.1e-2 in the loss): each fails; rotary on the
#              latent layer 0.18 beside the sound program's 0.155 on the
#              same weights is NOT told apart (after 120 steps at 5e-6 the
#              latent layer's scores are near uniform, and a rotation of
#              both q and the one shared key part moves them by less than
#              the rounding).
#   loss       TOLERANCE 2e-3, the accepted LM cells': 2.6e-5 .. 5.2e-4 in
#              the 14 states (3.8 x of room); the all-bfloat16 reference
#              7.9e-3 .. 3.5e-2: it fails by this limit too.
#
TOLERANCE = 2e-3
LIMITS = {"cost_rms_over_bf16": 0.6}

_HP_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "first_k_dense_replace", "moe_layer_freq", "linear_attn_config",
            "num_attention_heads", "num_key_value_heads", "kv_lora_rank",
            "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "mla_use_nope", "num_experts_per_token",
            "num_shared_experts", "moe_router_activation_func",
            "moe_renormalize", "routed_scaling_factor", "num_expert_group",
            "topk_group", "rms_norm_eps", "rope_theta", "rope_scaling",
            "tie_word_embeddings")


def _arch(cfg):
    """The numbers the architecture is made of, under the builder's names:
    the router's width is `num_experts`, the file's count of held experts
    `num_local_experts`."""
    arch = {k: cfg[k] for k in _HP_KEYS}
    arch["num_experts"] = int(cfg["share"]["router_experts"])
    arch["num_local_experts"] = int(cfg["num_experts"])
    arch["expert_offset"] = int(cfg["share"]["expert_offset"])
    return arch


def build(cfg, work, mesh=None, forward_only=False):
    from paddle_tpu.models import kimi_linear

    class HP(kimi_linear.KimiLinearConfig):
        pass

    for k, v in _arch(cfg).items():
        setattr(HP, k, v)
    train = cfg["train"]
    main, startup, feeds, fetches = kimi_linear.kimi_linear_lm_program(
        HP, seq_len=int(work["seq_len"]), lr=float(train["learning_rate"]),
        is_test=forward_only, use_bf16=bool(train["use_bf16"]), mesh=mesh,
        bias_rate=train["expert_bias_rate"],
        bias_max_step=train["expert_bias_max_step"])
    return {"main": main, "startup": startup, "feeds": feeds,
            "loss": fetches[0]}


def make_batch(cfg, work, seed):
    """Full-length packed sequences of random tokens with p(k) ~ 1/k over
    the vocabulary slice, as the other LM adapters make them; labels are
    the ids shifted by one; every position counts."""
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


# the chunk the op's work is counted at: the published kernel's (and
# ops/kda_ops.CHUNK; benchmark/tests holds the two together)
KDA_CHUNK = 64


def _kinds(cfg):
    """("kda" | "mla") for every layer, from the two 1-based lists."""
    la = cfg["linear_attn_config"]
    return ["kda" if i + 1 in la["kda_layers"] else "mla"
            for i in range(cfg["num_hidden_layers"])]


def _held_rows(cfg, work):
    """Rows one expert layer's held experts expect in a step: N k E_held /
    E, every expert equally likely."""
    return (int(work["batch"]) * int(work["seq_len"])
            * cfg["num_experts_per_token"] * cfg["num_experts"]
            / float(cfg["share"]["router_experts"]))


def kda_core_cost(cfg, work):
    """What one kda_attention op must do in a step, from the shapes: the
    SAME work whatever implements it, by the chunkwise form at C = 64
    written out.  A token a head, forward: against its chunk three
    products C x dk wide (A_kk, A_qk and the solve's W) and two C x dv wide
    (the solve's U0, and A_qk U): 2 C (3 dk + 2 dv); against the carried
    state three dk x dv products (W S, Q S, K^T U): 6 dk dv.  The
    triangular solve's own C^3 / 3 a chunk and the decays' exponentials
    are left out (they are the implementation's), and so is the causal
    half of the C x C products.  Forward and backward without
    recomputation: three times that.  Bytes: q, k, v and the result in
    bfloat16, g in float32 and beta, read or written once forward; the
    same and every gradient once backward."""
    la = cfg["linear_attn_config"]
    rows = int(work["batch"]) * int(work["seq_len"]) * la["num_heads"]
    dk = dv = la["head_dim"]
    fwd = rows * (2.0 * KDA_CHUNK * (3 * dk + 2 * dv) + 6.0 * dk * dv)
    once = rows * (2.0 * (2 * dk + 2 * dv) + 4.0 * dk + 4.0)
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * once}


def mla_core_cost(cfg, work):
    """What the fused_attention op of the latent-attention layer must do in
    a step: kanana2_lm.mla_core_cost's count (the causal half, scores 192
    wide over 128-wide values, backward twice the forward, q, k, v, the
    result and their gradients once in bf16)."""
    b, t = int(work["batch"]), int(work["seq_len"])
    h = cfg["num_attention_heads"]
    d_qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    dv = cfg["v_head_dim"]
    fwd = 2.0 * (b * h * t * t / 2.0) * (d_qk + dv)
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 2.0 * b * h * t * (2 * 2 * d_qk + 2 * 2 * dv)}


def expert_matmul_cost(cfg, work):
    """What one layer's two grouped matmuls must do in a step, from the
    shapes, over the rows the held experts EXPECT (N k E_held / E; the dead
    part of the static row buffer is no work) and the held experts'
    weights: 6 rows d f operations forward (through [d, 2f] and [f, d])
    and twice that backward; bytes with every held expert's weights read
    once per matmul (and their gradient written once), and the rows of
    each matmul's operands and result read or written once, in bf16."""
    rows = _held_rows(cfg, work)
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    fwd = 6.0 * rows * d * f
    weights = 2.0 * e * 3 * d * f
    row_bytes = 2.0 * rows * ((d + 2 * f) + (f + d))
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * (weights + row_bytes)}


def forward_flops(cfg, work):
    """Operations of one forward pass by part: matmuls, and the KDA cores
    by `kda_core_cost`.  The experts are counted over the rows this chip's
    share of them expects, not over all N k routed rows: the others run on
    chips that are not here."""
    rows = int(work["batch"]) * int(work["seq_len"])
    d, la = cfg["hidden_size"], cfg["linear_attn_config"]
    n_kda = _kinds(cfg).count("kda")
    n_mla = cfg["num_hidden_layers"] - n_kda
    width, dh = la["num_heads"] * la["head_dim"], la["head_dim"]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    dense = cfg["first_k_dense_replace"]
    moe = cfg["num_hidden_layers"] - dense
    fe = cfg["moe_intermediate_size"]
    return {
        # q, k, v and o at heads x head_dim, the decay's and the gate's
        # two low-rank pairs, beta
        "kda_projections": n_kda * 2.0 * rows * (
            4 * d * width + 2 * (d * dh + dh * width) + d * la["num_heads"]),
        "kda_cores": n_kda * kda_core_cost(cfg, work)["flops_forward"],
        "mla_projections": n_mla * 2.0 * rows * (
            d * h * (nope + rot) + d * (r + rot) + r * h * (nope + dv)
            + h * dv * d),
        "mla_core": n_mla * mla_core_cost(cfg, work)["flops_forward"],
        "dense_mlp": dense * 3 * 2.0 * rows * d * cfg["intermediate_size"],
        "shared_expert": moe * 3 * 2.0 * rows * d * (
            cfg["num_shared_experts"] * fe),
        "router": moe * 2.0 * rows * d * cfg["share"]["router_experts"],
        "experts": moe * expert_matmul_cost(cfg, work)["flops_forward"],
        "head": 2.0 * rows * d * cfg["vocab_size"],
    }


def model_flops(cfg, work):
    """Forward + backward (3 x forward), recomputation never counted."""
    return 3.0 * sum(forward_flops(cfg, work).values())


# --------------------------------------------------------------------------
# plain reference (this file's own copy of paddle_tpu/models/
# kimi_linear_reference.py's equations; benchmark/tests holds the two
# together): float32, "highest", Kimi Delta Attention as the
# token-by-token recurrence in a lax.scan over T (no chunk, no solve), the
# convolution as shifted products, the latent attention's [T, T] softmax
# under a mask built densely, one head's rows at a time, the held experts
# as a loop over a boolean mask (what the absent ones would add is left
# out, as in the program), an untied head.  No auxiliary loss; no document
# mask in a packed sequence.
# --------------------------------------------------------------------------
# One deliberate error each, for tools/kanana2_departures.py and the
# tests: the comparison that decides `correct` has to fail on every one on
# weights where it shows (tests/test_kimi_linear_model.py).
DEPARTURES = (
    "decay_per_head",      # a head's mean log-decay on all its channels
    "no_decay",            # g = 0: the plain delta rule
    "no_beta",             # beta = 1
    "no_delta_correction",  # k k^T left out: gated linear attention
    "no_qk_l2norm",        # q and k not normalised
    "conv_one_ahead",      # the filter's last tap reads token t + 1
    "no_out_gate",         # the sigmoid output gate left out
    "rope_on_mla",         # rotary on the latent attention's 64-wide parts
    "no_routed_scaling",   # routed_scaling_factor left out
)


def reference(cfg, params, batch, departure=None, dtype="float32"):
    """-> (loss, rows [B, T] float32: every token's cross-entropy), on the
    host's CPU device where jax has one: on the chip the reference would
    have to fit beside 9 GB of training state.  `departure` is one of
    DEPARTURES (a wrong model), `dtype` "bfloat16" the stated precision's
    neighbour below (weights, activations, router and matmuls all
    bfloat16): what the comparison has to catch, never what the benchmark
    compares with."""
    import jax
    import jax.numpy as jnp

    if departure is not None and departure not in DEPARTURES:
        raise ValueError("unknown departure %r" % (departure,))

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
        loss, rows = jax.jit(lambda w, b: _loss(
            arch, [x.astype(dtype) for x in w], b, departure))(weights, batch)
    return float(loss), np.asarray(rows, "float32")


# --------------------------------------------------------------------------
# the comparison that decides `correct` (kanana2_lm's and trinity_lm's)
# --------------------------------------------------------------------------
def program_rows():
    """What the program's `is_test` build left in the scope it last ran in
    (loops/train.py compares inside its `scope_guard`): every token's
    cost, [B, T]; None where the scope holds none."""
    import paddle_tpu as fluid
    from paddle_tpu.models import kimi_linear

    rows = fluid.global_scope().find_var(kimi_linear.EVAL_ROWS)
    return None if rows is None else np.asarray(rows, "float64")


def _rms(a, b):
    return float(np.sqrt(np.mean(np.square(
        np.asarray(a, "float64") - np.asarray(b, "float64")))))


def bf16_unit(cfg, params, batch, exact_rows=None, bf16_rows=None):
    """The unit the paired reading is in: the root mean square of what the
    all-bfloat16 reference's rows differ by from the exact float32
    reference's, on these weights and rows."""
    if exact_rows is None:
        exact_rows = reference(cfg, params, batch)[1]
    if bf16_rows is None:
        bf16_rows = reference(cfg, params, batch, dtype="bfloat16")[1]
    return _rms(bf16_rows, exact_rows)


def compare(cfg, params, batch, departure=None, dtype="float32", unit=None):
    """-> (what the harness is told, the reference's loss, the readings).
    Where the scope holds the rows of a program that just ran on these
    weights and rows (the harness's comparison does; a call on weights
    alone does not, and its readings are None), the harness is told NaN,
    which no tolerance admits, if a paired reading is over its limit.
    `unit`: a `bf16_unit` of the same weights and rows, where several
    comparisons share one."""
    loss, ref_rows = reference(cfg, params, batch, departure, dtype)
    got = program_rows()
    if got is None:
        return loss, loss, None
    if got.shape != ref_rows.shape:
        raise ValueError("the scope's rows %s are not of this batch %s"
                         % (got.shape, ref_rows.shape))
    if unit is None:
        mine = ref_rows if departure is None else None
        unit = bf16_unit(cfg, params, batch,
                         mine if dtype == "float32" else None,
                         mine if dtype == "bfloat16" else None)
    cost_rms = _rms(got, ref_rows)
    found = {"cost_rms": cost_rms, "bf16_unit": unit,
             "cost_rms_over_bf16": cost_rms / max(unit, 1e-30)}
    within = all(found[k] <= LIMITS[k] for k in LIMITS)
    return (loss if within else float("nan")), loss, found


def reference_loss(cfg, params, batch, departure=None, dtype="float32"):
    """The plain reference's loss on these weights and rows, or NaN (see
    `compare`); the readings go to stderr as one JSON line."""
    import json
    import sys

    told, loss, found = compare(cfg, params, batch, departure, dtype)
    if found is not None:
        print("kimi_linear_lm reference: %s" % json.dumps(dict(
            found, limits=LIMITS, reference_loss=loss, departure=departure,
            dtype=dtype)), file=sys.stderr, flush=True)
    return told


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope_pairs(x, theta):
    """x [..., T, D]: the pair (x[2i], x[2i+1]) turned by t theta^(-2i/D)
    (the `rope_on_mla` departure alone)."""
    import jax.numpy as jnp

    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def _loss(m, weights, batch, departure=None):
    import jax
    import jax.numpy as jnp

    d, la = m["hidden_size"], m["linear_attn_config"]
    n, dh, taps = la["num_heads"], la["head_dim"], la["short_conv_kernel_size"]
    h, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rot, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                     m["v_head_dim"])
    eps = m["rms_norm_eps"]
    k_top, f_moe = m["num_experts_per_token"], m["moe_intermediate_size"]
    held, offset = m["num_local_experts"], m["expert_offset"]
    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def conv_silu(x, filt):
        t = x.shape[1]
        ahead = int(departure == "conv_one_ahead")
        xp = jnp.pad(x, ((0, 0), (taps - 1 - ahead, ahead), (0, 0)))
        return jax.nn.silu(sum(xp[:, j:j + t] * filt[:, j]
                               for j in range(taps)))

    def kda(x):
        wq, wk, wv = (take(d, n * dh) for _ in range(3))
        wfa, wfb, dt_bias = take(d, dh), take(dh, n * dh), take(n * dh)
        wga, wgb, wb = take(d, dh), take(dh, n * dh), take(d, n)
        fq, fk, fv = (take(n * dh, taps) for _ in range(3))
        a_log, o_gain, wo = take(n, 1), take(dh), take(n * dh, d)
        bsz, t, _ = x.shape

        def heads(y):
            return y.reshape(bsz, t, n, dh)

        def l2norm(y):
            if departure == "no_qk_l2norm":
                return y
            return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True)
                                     + jnp.asarray(1e-6, y.dtype))

        q = l2norm(heads(conv_silu(x @ wq, fq))) * jnp.asarray(
            dh ** -0.5, x.dtype)
        key = l2norm(heads(conv_silu(x @ wk, fk)))
        v = heads(conv_silu(x @ wv, fv))
        g = -jnp.exp(a_log) * heads(jax.nn.softplus(
            (x @ wfa) @ wfb + dt_bias))
        if departure == "decay_per_head":
            g = jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape)
        if departure == "no_decay":
            g = jnp.zeros_like(g)
        beta = jax.nn.sigmoid(x @ wb)
        if departure == "no_beta":
            beta = jnp.ones_like(beta)

        def step(s, xs):  # one token: s [B, n, dh, dh]
            qt, kt, vt, gt, bt = xs
            s = jnp.exp(gt)[..., None] * s
            old = (jnp.zeros_like(vt) if departure == "no_delta_correction"
                   else jnp.einsum("bhc,bhcv->bhv", kt, s))
            s = s + kt[..., None] * (bt[..., None] * (vt - old))[..., None, :]
            return s, jnp.einsum("bhc,bhcv->bhv", qt, s)

        _, o = jax.lax.scan(
            step, jnp.zeros((bsz, n, dh, dh), x.dtype),
            [jnp.moveaxis(a, 1, 0) for a in (q, key, v, g, beta)])
        o = _rms_norm(jnp.moveaxis(o, 0, 1), o_gain, eps)
        if departure != "no_out_gate":
            o = o * jax.nn.sigmoid(heads((x @ wga) @ wgb))
        return o.reshape(bsz, t, n * dh) @ wo

    def mla(x):
        wq, wkva = take(d, h * (nope + rot)), take(d, r + rot)
        kv_norm, wkvb = take(r), take(r, h * (nope + dv))
        wo = take(h * dv, d)
        bsz, t, _ = x.shape
        # [H, B, T, .]: one head at a time
        q = (x @ wq).reshape(bsz, t, h, nope + rot).transpose(2, 0, 1, 3)
        latent = x @ wkva
        k_s = latent[..., r:]  # [B, T, rot]: ONE for all heads
        if departure == "rope_on_mla":
            theta = float(m["rope_theta"])
            q = jnp.concatenate(
                [q[..., :nope], _rope_pairs(q[..., nope:], theta)], -1)
            k_s = _rope_pairs(k_s, theta)
        kv = (_rms_norm(latent[..., :r], kv_norm, eps) @ wkvb).reshape(
            bsz, t, h, nope + dv).transpose(2, 0, 1, 3)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def head(qkv):
            qh, kvh = qkv
            key = jnp.concatenate([kvh[..., :nope], k_s], -1)
            s = (jnp.einsum("bqd,bkd->bqk", qh, key)
                 * (nope + rot) ** -0.5).astype(jnp.float32)
            s = jnp.where(causal, s, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(s, -1).astype(qh.dtype),
                              kvh[..., nope:])

        ctx = jax.lax.map(head, (q, kv))  # [H, B, T, dv]
        return ctx.transpose(1, 2, 0, 3).reshape(bsz, t, h * dv) @ wo

    def mlp(x, f):
        w1, w3, w2 = take(d, f), take(d, f), take(f, d)
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def routed(x):
        router, bias = take(d, m["num_experts"]), take(m["num_experts"])
        gate_up, down = take(held, d, 2 * f_moe), take(held, f_moe, d)
        x2 = x.reshape(-1, d)
        s = jax.nn.sigmoid(x2 @ router)
        _, top_e = jax.lax.top_k(s + bias, k_top)
        top_p = jnp.take_along_axis(s, top_e, -1)
        if m["moe_renormalize"]:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
        if departure != "no_routed_scaling":
            top_p = top_p * m["routed_scaling_factor"]
        y = jnp.zeros_like(x2)
        for local in range(held):
            chosen = top_e == offset + local
            weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
            gu = x2 @ gate_up[local]
            out = (jax.nn.silu(gu[:, :f_moe]) * gu[:, f_moe:]) @ down[local]
            y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out,
                              0.0)
        return y.reshape(x.shape)

    x = take(m["vocab_size"], d)[jnp.asarray(batch["ids"])]
    for i in range(m["num_hidden_layers"]):
        hidden = _rms_norm(x, take(d), eps)
        if i + 1 in la["kda_layers"]:
            x = x + kda(hidden)
        elif i + 1 in la["full_attn_layers"]:
            x = x + mla(hidden)
        else:
            raise ValueError("layer %d has no mixer" % (i + 1))
        hidden = _rms_norm(x, take(d), eps)
        if i < m["first_k_dense_replace"]:
            x = x + mlp(hidden, m["intermediate_size"])
        else:
            y = routed(hidden)
            if m["num_shared_experts"]:
                y = y + mlp(hidden, m["num_shared_experts"] * f_moe)
            x = x + y
    logits = _rms_norm(x, take(d), eps) @ take(d, m["vocab_size"])
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    logits = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"])
    rows = lse - picked
    return (rows * w).sum() / w.sum(), rows
