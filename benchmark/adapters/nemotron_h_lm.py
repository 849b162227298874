"""Adapter: NVIDIA-Nemotron-3-Nano-30B-A3B (NVIDIA; model type
`nemotron_h`) trained through
paddle_tpu.models.nemotron_h.nemotron_h_lm_program.  See transformer_wmt.py
for what an adapter is.  The configuration file keeps the widths under the
keys of the published config.json, at its top level; `n_routed_experts`
there counts the experts this chip HOLDS of each expert layer
(model-configs guide, section 4), `share` says over how many the router
chooses and where the held range starts.

A stack whose layers differ in kind: `forward_flops` reads
`hybrid_override_pattern` and counts a Mamba-2 layer's two projections and
its scan (`ssd_core_cost`: the chunkwise form at Q = 128 whatever
implements the op), the attention layer's projections and its core over
the causal half (T^2 / 2 pairs a head, trinity_lm's convention), and an
expert layer's router, shared expert and held experts, TWO matmuls an
expert (`expert_matmul_cost`: 4 rows d f forward, 12 a step, not 18).  Like
the other share-holding adapters it counts the held experts' EXPECTED
rows, N k E_held / E (even routing), whatever a step had;
`moe_rows_held_share` (readers/moe_held_stat.py) is the counter that says
what it had.
"""

import numpy as np

# What decides `correct` here, on the sampled row after the window:
# qwen3_next_lm's comparison, PAIRED readings under LIMITS and the harness's
# own |program loss - reference loss| <= TOLERANCE.  The forward-only
# program leaves every token's cost in the scope (`nemotron_h.EVAL_ROWS`);
# a reading is a norm of its differences from the reference's rows in units
# of the same norm of what the all-bfloat16 reference's rows differ by from
# the exact float32 one's ON THE SAME WEIGHTS (`bf16_unit`: one more
# reference a comparison).  `reference_loss` answers NaN, which no
# tolerance admits, where a reading is over its limit.  The stated
# precision: bf16 AMP matmuls and bf16 x, B, C into the scan's products; f32
# masters, router, norm statistics, dt, dt A and its running sums, every exp
# of them, the carried state, softmax and cross-entropy.
#
# TWO norms, because ONE cannot do both jobs here (my chip runs, PR 57: the
# cell on eighteen seeds, `tools/nemotron_h_departures.py` on two; PERF.md
# section 4, "Nemotron-3-Nano's cut", has the table).  The rows' differences
# are heavy-tailed (kurtosis 70-130 in four dumped states: a few tokens
# carry the sum of squares), so the root mean square of a SOUND program
# reads 0.61-0.80 of the all-bfloat16 reference's and swings by a quarter
# from seed to seed, where Qwen3-Next's reads 0.34-0.51: no limit stands
# between that and 1.0 with room on both sides.  The MEDIAN of the absolute
# differences reads 0.431 .. 0.465 in the 13 states read: the
# typical token of the stated precision lies 2.2 x closer to the exact
# reference than the all-bfloat16 one's, steadily.
#
#   cost_median_over_bf16   limit 0.67 = the geometric mean of the sound
#              program's 0.45 and the all-bfloat16 reference's 1.0 (1.015 of
#              its own unit against the program's rows): 1.44 x over the
#              largest sound reading, 1.5 x under.  THE limit the precision
#              below fails; rotary on the attention layer reads 0.92 and
#              weights from s + b 1.11 by it, the other six wrong models
#              11 .. 198.
#   cost_rms_over_bf16      limit 1.5: there for the wrong models, which move
#              the tails the median does not see.  Sound 0.61-0.80 (1.9 x of
#              room); the smallest wrong model it is there for, no 2.5, 5.0;
#              one group in the gated norm 8.2, head j reading group j mod 8
#              8.2, norm before the gate 16, relu for relu^2 27, no D skip
#              53.  The all-bfloat16 reference reads 1.01 and passes THIS
#              limit; it fails the median's.
#   loss       ISSUE 57 asked for the accepted LM cells' 2e-3.  NOT kept:
#              the sound program reads 3.4e-5 .. 2.9e-3 in 18 states,
#              always ABOVE the reference (noise of 0.05 a token in the
#              logits raises a cross-entropy by about half its variance), so
#              2e-3 leaves no room; 1e-2 (qwen3_next_lm's, an accepted
#              cell's) is 3.5 x the largest.  The all-bfloat16 reference reads
#              3.7e-3 .. 6.3e-3 and passes it; weights from s + b 1.7e-2
#              fails it.
#   NOT caught on the chip at these weights (pinned on the CPU where each
#   shows, tests/test_nemotron_h_model.py): the carried state or dt and
#   dt A rounded to bfloat16 ALONE (median 0.482 / 0.431 against the sound
#   0.431 in the same state: with the trunk's bf16 matmuls beside them
#   they are a tenth of the noise).
#
TOLERANCE = 1e-2
LIMITS = {"cost_median_over_bf16": 0.67, "cost_rms_over_bf16": 1.5}

_HP_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
            "hybrid_override_pattern", "mamba_num_heads", "mamba_head_dim",
            "n_groups", "ssm_state_size", "conv_kernel", "chunk_size",
            "use_conv_bias", "mamba_proj_bias", "mamba_hidden_act",
            "time_step_min", "time_step_max", "time_step_floor",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "attention_bias", "num_experts_per_tok", "n_shared_experts",
            "moe_intermediate_size", "moe_shared_expert_intermediate_size",
            "n_group", "topk_group", "norm_topk_prob",
            "routed_scaling_factor", "mlp_hidden_act", "mlp_bias",
            "layer_norm_epsilon", "rescale_prenorm_residual",
            "tie_word_embeddings")
KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


def _arch(cfg):
    """The numbers the architecture is made of, under the builder's names:
    the router's width is `n_routed_experts`, the file's count of held
    experts `num_local_experts`."""
    arch = {k: cfg[k] for k in _HP_KEYS}
    arch["n_routed_experts"] = int(cfg["share"]["router_experts"])
    arch["num_local_experts"] = int(cfg["n_routed_experts"])
    arch["expert_offset"] = int(cfg["share"]["expert_offset"])
    return arch


def build(cfg, work, mesh=None, forward_only=False):
    from paddle_tpu.models import nemotron_h

    class HP(nemotron_h.NemotronHConfig):
        pass

    for k, v in _arch(cfg).items():
        setattr(HP, k, v)
    train = cfg["train"]
    main, startup, feeds, fetches = nemotron_h.nemotron_h_lm_program(
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


def _kinds(cfg):
    """("mamba2" | "experts" | "attention") for every layer."""
    return [KINDS[ch] for ch in cfg["hybrid_override_pattern"]]


def _held_rows(cfg, work):
    """Rows one expert layer's held experts expect in a step: N k E_held /
    E, every expert equally likely."""
    return (int(work["batch"]) * int(work["seq_len"])
            * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
            / float(cfg["share"]["router_experts"]))


def ssd_core_cost(cfg, work):
    """What one mamba2_scan op must do in a step, from the shapes: the SAME
    work whatever implements it, by the chunkwise form at Q = `chunk_size`
    written out.  A token, forward: a group's C B^T against its chunk, 2 Q
    N; a head the masked product against the chunk's dt x, 2 Q P, and the
    state read (C S^T) and written ((dt x)^T B), 4 N P.  The decay's
    exponentials and the causal half of the Q x Q products are left out.
    Forward and backward without recomputation: three times that.  Bytes:
    x and the result at the heads, B and C at the groups in bfloat16, dt in
    float32, read or written once forward; the same and every gradient once
    backward."""
    rows = int(work["batch"]) * int(work["seq_len"])
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    fwd = rows * (g * 2.0 * q * n + h * (2.0 * q * p + 4.0 * n * p))
    once = rows * (2.0 * (2 * h * p + 2 * g * n) + 4.0 * h)
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * once}


def full_core_cost(cfg, work):
    """What the attention layer's fused_attention op must do in a step:
    QK^T and PV over the causal half at `num_attention_heads` heads of
    `head_dim` (the KV heads repeated: the work is the query heads'),
    backward twice the forward without recomputation, q, k, v, the result
    and their gradients once in bf16: qwen3_next_lm.full_core_cost's
    convention."""
    b, t = int(work["batch"]), int(work["seq_len"])
    h, dh = cfg["num_attention_heads"], cfg["head_dim"]
    fwd = 2.0 * (b * h * t * t / 2.0) * (dh + dh)
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 2.0 * b * h * t * (2 * 2 * dh + 2 * 2 * dh)}


def expert_matmul_cost(cfg, work):
    """What one layer's two grouped matmuls must do in a step, from the
    shapes, over the rows the held experts EXPECT (N k E_held / E; the dead
    part of the static row buffer is no work) and the held experts'
    weights: an ungated expert is TWO matmuls, 4 rows d f operations
    forward (through [d, f] and [f, d]) and twice that backward; bytes with
    every held expert's weights read once per matmul (and their gradient
    written once), and the rows of each matmul's operands and result read
    or written once, in bf16."""
    rows = _held_rows(cfg, work)
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["n_routed_experts"])
    fwd = 4.0 * rows * d * f
    weights = 2.0 * e * 2 * d * f
    row_bytes = 2.0 * rows * ((d + f) + (f + d))
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * (weights + row_bytes)}


def forward_flops(cfg, work):
    """Operations of one forward pass by part, for a stack whose layers
    differ in kind: matmuls, the scans by `ssd_core_cost` and the attention
    core by `full_core_cost` (the causal half).  The experts are counted
    over the rows this chip's share of them expects, not over all N k
    routed rows: the others run on chips that are not here."""
    rows = int(work["batch"]) * int(work["seq_len"])
    d, kinds = cfg["hidden_size"], _kinds(cfg)
    n_m, n_a, n_e = (kinds.count(k) for k in ("mamba2", "attention",
                                              "experts"))
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    ha, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    return {
        # [z | xBC | dt] in, y out
        "mamba_projections": n_m * 2.0 * rows * d * (
            3 * h * p + 2 * g * n + h),
        "mamba_cores": n_m * ssd_core_cost(cfg, work)["flops_forward"],
        # q and o at the query heads, k and v at the KV heads
        "attention_projections": n_a * 2.0 * rows * d * (
            2 * ha * dh + 2 * hkv * dh),
        "attention_core": n_a * full_core_cost(cfg, work)["flops_forward"],
        "shared_expert": n_e * 2.0 * rows * d * (
            2 * cfg["n_shared_experts"]
            * cfg["moe_shared_expert_intermediate_size"]),
        "router": n_e * 2.0 * rows * d * cfg["share"]["router_experts"],
        "experts": n_e * expert_matmul_cost(cfg, work)["flops_forward"],
        "head": 2.0 * rows * d * cfg["vocab_size"],
    }


def model_flops(cfg, work):
    """Forward + backward (3 x forward), recomputation never counted."""
    return 3.0 * sum(forward_flops(cfg, work).values())


# --------------------------------------------------------------------------
# plain reference (this file's own copy of paddle_tpu/models/
# nemotron_h_reference.py's equations; benchmark/tests holds the two
# together): float32, "highest", the Mamba-2 scan as the token-by-token
# recurrence in a lax.scan over T (never the chunkwise form), the
# convolution as shifted products, the attention's [T, T] softmax under a
# mask built densely, one head's rows at a time, the held experts as a loop
# over a boolean mask (what the absent ones would add is left out, as in the
# program), an untied head.  No auxiliary loss; no document mask in a packed
# sequence.
# --------------------------------------------------------------------------
# One deliberate error each, for tools/nemotron_h_departures.py and the
# tests: the comparison that decides `correct` has to fail on every one on
# weights where it shows (tests/test_nemotron_h_model.py).
DEPARTURES = (
    "state_bf16",          # the carried state rounded to bfloat16 a token
    "dt_bf16",             # dt and dt A rounded to bfloat16
    "no_d_skip",           # D = 0
    "norm_before_gate",    # the grouped norm first, silu(z) after it
    "one_norm_group",      # ONE group of all d_inner channels in that norm
    "relu_not_relu2",      # relu in place of relu^2, every expert
    "weights_from_biased_scores",  # the top-k weights s + b, not s
    "no_routed_scaling",   # routed_scaling_factor left out
    "rotary_on",           # rotate-half rotary on q and k at rope_theta
    "group_mod",           # head j reads group j mod G, not j // (H / G)
)


def reference(cfg, params, batch, departure=None, dtype="float32"):
    """-> (loss, rows [B, T] float32: every token's cross-entropy), on the
    host's CPU device where jax has one: on the chip the reference would
    have to fit beside 10.7 GB of training state.  `departure` is one of
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
    from paddle_tpu.models import nemotron_h

    rows = fluid.global_scope().find_var(nemotron_h.EVAL_ROWS)
    return None if rows is None else np.asarray(rows, "float64")


def _differences(a, b):
    return np.abs(np.asarray(a, "float64") - np.asarray(b, "float64"))


def _rms(a, b):
    return float(np.sqrt(np.mean(np.square(_differences(a, b)))))


def _median(a, b):
    return float(np.median(_differences(a, b)))


def bf16_unit(cfg, params, batch, exact_rows=None, bf16_rows=None):
    """The units the paired readings are in: what the all-bfloat16
    reference's rows differ by from the exact float32 reference's, on these
    weights and rows: {"rms": the root mean square, "median": the median
    of the absolute differences}."""
    if exact_rows is None:
        exact_rows = reference(cfg, params, batch)[1]
    if bf16_rows is None:
        bf16_rows = reference(cfg, params, batch, dtype="bfloat16")[1]
    return {"rms": _rms(bf16_rows, exact_rows),
            "median": _median(bf16_rows, exact_rows)}


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
    cost_rms, cost_median = _rms(got, ref_rows), _median(got, ref_rows)
    found = {"cost_rms": cost_rms, "bf16_unit": unit["rms"],
             "cost_rms_over_bf16": cost_rms / max(unit["rms"], 1e-30),
             "cost_median": cost_median, "bf16_median_unit": unit["median"],
             "cost_median_over_bf16": cost_median / max(unit["median"],
                                                        1e-30)}
    within = all(found[k] <= LIMITS[k] for k in LIMITS)
    return (loss if within else float("nan")), loss, found


def reference_loss(cfg, params, batch, departure=None, dtype="float32"):
    """The plain reference's loss on these weights and rows, or NaN (see
    `compare`); the readings go to stderr as one JSON line."""
    import json
    import sys

    told, loss, found = compare(cfg, params, batch, departure, dtype)
    if found is not None:
        print("nemotron_h_lm reference: %s" % json.dumps(dict(
            found, limits=LIMITS, reference_loss=loss, departure=departure,
            dtype=dtype)), file=sys.stderr, flush=True)
    return told


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rotate(x, theta):
    """x [..., T, dh]: rotate-half rotary over the whole head, the pair
    (i, i + dh / 2) turned by t theta^(-2i / dh) (the `rotary_on`
    departure alone: the model has none)."""
    import jax.numpy as jnp

    width = x.shape[-1]
    half = width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _loss(m, weights, batch, departure=None):
    import jax
    import jax.numpy as jnp

    d, eps = m["hidden_size"], m["layer_norm_epsilon"]
    hm, p = m["mamba_num_heads"], m["mamba_head_dim"]
    g, n, taps = m["n_groups"], m["ssm_state_size"], m["conv_kernel"]
    inner, conv_width = hm * p, hm * p + 2 * g * n
    h, hkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"])
    k_top, f_moe = m["num_experts_per_tok"], m["moe_intermediate_size"]
    f_shared = m["n_shared_experts"] * m["moe_shared_expert_intermediate_size"]
    held, offset = m["num_local_experts"], m["expert_offset"]
    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def conv_silu(x, filt, bias):
        t = x.shape[1]
        xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(xp[:, j:j + t] * filt[:, j]
                               for j in range(taps)) + bias)

    def mamba2(x):
        w_in, filt = take(d, 2 * inner + 2 * g * n + hm), take(conv_width,
                                                               taps)
        conv_bias = take(conv_width) if m["use_conv_bias"] else 0.0
        dt_bias, a_log, skip = take(hm), take(hm), take(hm)
        gain, w_out = take(g, inner // g), take(inner, d)
        bsz, t, _ = x.shape

        def readers(y):  # [B, T, G, N] -> one a head
            if departure == "group_mod":
                return jnp.tile(y, (1, 1, hm // g, 1))
            return jnp.repeat(y, hm // g, axis=2)

        zxbcdt = x @ w_in
        z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-hm],
                      zxbcdt[..., -hm:])
        xbc = conv_silu(xbc, filt, conv_bias)
        xs = xbc[..., :inner].reshape(bsz, t, hm, p)
        bm = readers(xbc[..., inner:inner + g * n].reshape(bsz, t, g, n))
        cm = readers(xbc[..., inner + g * n:].reshape(bsz, t, g, n))
        # float32 whatever the trunk, as the model states it (the
        # all-bfloat16 reference rounds the trunk, not these)
        dt = jax.nn.softplus(dt.astype(jnp.float32)
                             + dt_bias.astype(jnp.float32))
        da = dt * -jnp.exp(a_log.astype(jnp.float32))
        if departure == "dt_bf16" or x.dtype == jnp.bfloat16:
            dt, da = (v.astype(jnp.bfloat16).astype(jnp.float32)
                      for v in (dt, da))
        if departure == "no_d_skip":
            skip = jnp.zeros_like(skip)
        state_dtype = (jnp.bfloat16 if departure == "state_bf16"
                       else x.dtype)

        def step(s, v):  # one token: s [B, H, P, N]
            xt, dtt, dat, bt, ct = v
            s = (jnp.exp(dat)[..., None, None] * s.astype(jnp.float32)
                 + ((dtt[..., None] * xt)[..., None]
                    * bt[..., None, :]).astype(jnp.float32)
                 ).astype(state_dtype)
            y = jnp.einsum("bhpn,bhn->bhp", s.astype(xt.dtype), ct)
            return s, y + skip[:, None].astype(xt.dtype) * xt

        _, y = jax.lax.scan(
            step, jnp.zeros((bsz, hm, p, n), state_dtype),
            [jnp.moveaxis(v, 1, 0) for v in (xs, dt, da, bm, cm)])
        y = jnp.moveaxis(y, 0, 1).reshape(bsz, t, inner).astype(x.dtype)
        groups = 1 if departure == "one_norm_group" else g

        def grouped(v):
            return _rms_norm(v.reshape(bsz, t, groups, inner // groups),
                             gain.reshape(groups, inner // groups),
                             eps).reshape(bsz, t, inner)

        if departure == "norm_before_gate":
            y = grouped(y) * jax.nn.silu(z)
        else:
            y = grouped(y * jax.nn.silu(z))
        return y @ w_out

    def attention(x):
        wq, wk, wv = take(d, h * dh), take(d, hkv * dh), take(d, hkv * dh)
        wo = take(h * dh, d)
        bsz, t, _ = x.shape

        def heads(y, count):  # -> [count, B, T, dh]: one head at a time
            y = y.reshape(bsz, t, count, dh).transpose(2, 0, 1, 3)
            if departure == "rotary_on":
                y = _rotate(y, float(m.get("rope_theta", 10000.0)))
            return y

        q = heads(x @ wq, h)
        key = jnp.repeat(heads(x @ wk, hkv), h // hkv, axis=0)
        v = jnp.repeat((x @ wv).reshape(bsz, t, hkv, dh).transpose(
            2, 0, 1, 3), h // hkv, axis=0)
        causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]

        def head(qkv):
            qh, kh, vh = qkv
            s = (jnp.einsum("bqd,bkd->bqk", qh, kh)
                 * dh ** -0.5).astype(jnp.float32)
            s = jnp.where(causal, s, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(s, -1).astype(qh.dtype), vh)

        ctx = jax.lax.map(head, (q, key, v))  # [H, B, T, dh]
        return ctx.transpose(1, 2, 0, 3).reshape(bsz, t, h * dh) @ wo

    def body(x2, w_up, w_down):
        up = jax.nn.relu(x2 @ w_up)
        return (up if departure == "relu_not_relu2" else up * up) @ w_down

    def experts(x):
        router, bias = take(d, m["n_routed_experts"]), take(
            m["n_routed_experts"])
        up, down = take(held, d, f_moe), take(held, f_moe, d)
        x2 = x.reshape(-1, d)
        s = jax.nn.sigmoid((x2 @ router).astype(jnp.float32))
        chooser = s + bias.astype(jnp.float32)
        _, top_e = jax.lax.top_k(chooser, k_top)
        top_p = jnp.take_along_axis(
            chooser if departure == "weights_from_biased_scores" else s,
            top_e, -1)
        if m["norm_topk_prob"]:
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
        if departure != "no_routed_scaling":
            top_p = top_p * m["routed_scaling_factor"]
        top_p = top_p.astype(x.dtype)
        y = jnp.zeros_like(x2)
        for local in range(held):
            chosen = top_e == offset + local
            weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
            y = y + jnp.where(chosen.any(-1, keepdims=True),
                              weight * body(x2, up[local], down[local]), 0.0)
        if m["n_shared_experts"]:
            y = y + body(x2, take(d, f_shared), take(f_shared, d))
        return y.reshape(x.shape)

    blocks = {"mamba2": mamba2, "attention": attention, "experts": experts}
    x = take(m["vocab_size"], d)[jnp.asarray(batch["ids"])]
    for ch in m["hybrid_override_pattern"]:
        x = x + blocks[KINDS[ch]](_rms_norm(x, take(d), eps))
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
