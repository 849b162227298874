"""Adapter: Laguna-XS.2 (poolside; model type `laguna`) trained through
paddle_tpu.models.laguna.laguna_lm_program.  See transformer_wmt.py for
what an adapter is.  The configuration file keeps the widths under the
keys of the published config.json, at its top level; `num_experts` there
counts the experts this chip HOLDS of each layer (model-configs guide,
section 4), `share` says over how many the router chooses and where the
held range starts.

The closed forms are PER LAYER KIND, because the kinds differ in their
number of query heads (`num_attention_heads_per_layer`): the projections
of layer l at H_l heads (q and o H_l x head_dim wide, the gate [d, H_l]),
a sliding_attention core over the band's visible pairs at ITS heads
(`window_core_cost`), a full_attention core over the causal half at its
own (`full_core_cost`), by the conventions trinity_lm.py states.  The held
experts are counted over the rows they EXPECT, N k E_held / E (even
routing), whatever a step had; `moe_rows_held_share` is the counter that
says what it had.
"""

import math

import numpy as np

# What decides `correct` here, on the sampled row (6,144 positions) after
# the window (130 steps of training at the issue's Adam 5e-6; 142 in a
# traced run): kanana2_lm's comparison with ONE paired reading under LIMITS,
# and the harness's own |program loss - reference loss| <= TOLERANCE.  The
# forward-only program leaves every token's cost in the scope
# (`laguna.EVAL_ROWS`); the reading is the median of the absolute
# differences between its rows and the reference's, in units of the same
# statistic of what the all-bfloat16 reference's rows differ by from the
# exact float32 one's ON THE SAME WEIGHTS (`bf16_unit`: one more reference a
# comparison).  `reference_loss` answers NaN, which no tolerance admits,
# where the reading is over its limit.  Why paired: a mean over 6,144 tokens
# averages bf16 rounding away, so the loss alone cannot tell the stated
# precision (bf16 AMP matmuls; f32 masters, router, gate, norm statistics,
# rotary tables, softmax and cross-entropy) from the one below it (the
# all-bfloat16 reference's loss is within 7.5e-4 .. 1.3e-3).  Readings on
# the chip at full width (my chip runs, PR 65: the issue's traffic on
# fourteen seeds, tools/kanana2_departures.py on seeds 77 and 4242 and, for
# the control, on three of the traffic's own seeds; PERF.md section 4 has
# the table):
#
#   cost_median_over_bf16   the program against the exact reference 0.425
#              and 0.464 in the tool's two states (absolute 4.5e-3 .. 4.8e-3
#              of a unit of 1.03e-2 .. 1.06e-2), from which the limit was
#              set, then 0.417 .. 0.486 in seven runs of the traffic; the
#              whole reference in bfloat16 1.014 and 1.025 in the tool's
#              two states and 0.996, 1.013, 1.015 on three of the traffic's
#              own seeds (2147480001, 1357924680, 2020202020: sound 0.417 ..
#              0.481 there).  The limit is the geometric mean of the two
#              nearest readings it was set from, 0.69: 1.42 x over the
#              largest sound reading and 1.44 x under the smallest
#              all-bfloat16 one.  A median does not see a fault on a
#              minority of tokens: a window off by one key of 512 is NOT
#              caught on the chip (0.446 and 0.485 beside the sound
#              program's 0.425 and 0.464); tests/test_laguna_model.py
#              holds it.
#   cost_rms_over_bf16   a READING, not a limit.  ISSUE 65 started from
#              Trinity-Mini's 0.75 on it and the sound program reads AT it:
#              0.653 .. 0.755 in sixteen states of sixteen seeds (`correct`
#              came out false at 0.755 under it), the all-bfloat16
#              reference 0.993 .. 1.042 in five: the root mean square is
#              made by the tail of tokens whose routing or largest logit
#              sits on a rounding's edge, which both precisions share, and
#              the two are 1.32 x apart, too close for a limit with room on
#              both sides.  A limit above the control (nemotron_h_lm keeps
#              1.5) would fail nothing that was measured here, so none is
#              set; the reading stays in the line.
#   loss       TOLERANCE 2e-3, the accepted LM cells': 9.5e-7 .. 5.1e-4 in
#              the sixteen states.  It decides nothing the paired reading
#              does not.
#   Wrong models, median | rms in units, both states: twelve of the
#              fourteen below fail the median's limit, all but a window
#              off by one key either way (above); the thinnest caught is
#              the softmax router, 3.9 | 2.7.
#
# tests/test_laguna_model.py pins every departure below and the
# all-bfloat16 reference on the CPU in float32 on weights where each shows,
# loss and paired costs.
TOLERANCE = 2e-3
LIMITS = {"cost_median_over_bf16": 0.69}

_HP_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_hidden_layers", "layer_types", "mlp_layer_types",
            "num_attention_heads_per_layer", "num_key_value_heads",
            "head_dim", "sliding_window", "rope_parameters", "gating",
            "num_experts_per_tok", "moe_routed_scaling_factor",
            "moe_apply_router_weight_on_input", "attention_bias",
            "rms_norm_eps", "max_position_embeddings", "tie_word_embeddings")


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
    from paddle_tpu.models import laguna

    class HP(laguna.LagunaConfig):
        pass

    for k, v in _arch(cfg).items():
        setattr(HP, k, v)
    train = cfg["train"]
    main, startup, feeds, fetches = laguna.laguna_lm_program(
        HP, seq_len=int(work["seq_len"]), lr=float(train["learning_rate"]),
        is_test=forward_only, use_bf16=bool(train["use_bf16"]), mesh=mesh)
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


def _held_rows(cfg, work):
    """Rows one expert layer's held experts expect in a step: N k E_held /
    E, every expert equally likely."""
    return (int(work["batch"]) * int(work["seq_len"])
            * cfg["num_experts_per_tok"] * cfg["num_experts"]
            / float(cfg["share"]["router_experts"]))


def core_pairs(t, window):
    """Query-key pairs a head's core covers at length t.  window 0 (a
    full_attention layer): the causal half, t^2 / 2, kanana2_lm's
    convention.  A window that reaches fewer keys than the sequence has:
    the visible pairs, query i seeing min(i + 1, window) keys: window t -
    window (window - 1) / 2."""
    if not window or window >= t:
        return t * t / 2.0
    return window * t - window * (window - 1) / 2.0


def _heads_of(cfg, kind):
    """The number of query heads the layers of `kind` have: one number a
    kind in the published lists, which is what lets a reader multiply one
    core's cost by the kind's ops."""
    heads = {h for h, k in zip(cfg["num_attention_heads_per_layer"],
                               cfg["layer_types"]) if k == kind}
    if len(heads) != 1:
        raise ValueError("%s layers have %s query heads: a core's cost is "
                         "one number a kind" % (kind, sorted(heads)))
    return heads.pop()


def _core_cost(cfg, work, heads, window):
    b, t = int(work["batch"]), int(work["seq_len"])
    dh = cfg["head_dim"]
    fwd = 2.0 * b * heads * core_pairs(t, window) * (dh + dh)
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 2.0 * b * heads * t * 8 * dh}


def window_core_cost(cfg, work):
    """What one fused_attention op of a sliding_attention layer must do in
    a step, from the shapes, at THAT kind's heads (64): QK^T and PV over
    the VISIBLE pairs (B H `core_pairs`), each contracting or producing
    head_dim, two operations a multiply-add; backward, without recomputing
    the scores, dV and dP, dQ and dK: twice the forward.  The same work
    whatever blocks the kernel visits: a 1024-block that sees 512 keys
    computes four times these pairs, and that is its own time.  Bytes: q,
    k, v, the result and their gradients, each read or written once in bf16
    as the op sees them ([B, H, T, head_dim]: the kv heads are repeated
    before it)."""
    return _core_cost(cfg, work, _heads_of(cfg, "sliding_attention"),
                      int(cfg["sliding_window"]))


def full_core_cost(cfg, work):
    """The same for one fused_attention op of a full_attention layer, at
    its kind's heads (48), over the causal half."""
    return _core_cost(cfg, work, _heads_of(cfg, "full_attention"), 0)


def forward_flops(cfg, work):
    """Operations of one forward pass by part: matmuls.  Attention's
    projections layer by layer at that layer's heads (q and o at H_l x
    head_dim, k and v at the kv heads, the gate [d, H_l]); a core over the
    pairs a query may see (`core_pairs`); the experts over the rows this
    chip's share of them expects, not over all N k routed rows: the others
    run on chips that are not here."""
    rows = int(work["batch"]) * int(work["seq_len"])
    d, kv, dh = (cfg["hidden_size"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    kinds = cfg["layer_types"]
    sliding = sum(k == "sliding_attention" for k in kinds)
    dense = sum(k == "dense" for k in cfg["mlp_layer_types"])
    sparse = len(cfg["mlp_layer_types"]) - dense
    gate = 1 if cfg["gating"] else 0

    def cores(layers, cost):  # a kind the cut holds no layer of costs nothing
        return layers * cost(cfg, work)["flops_forward"] if layers else 0.0

    return {
        "attn_projections": sum(
            2.0 * rows * d * (dh * (2 * h + 2 * kv) + gate * h)
            for h in cfg["num_attention_heads_per_layer"]),
        "window_cores": cores(sliding, window_core_cost),
        "full_cores": cores(len(kinds) - sliding, full_core_cost),
        "dense_mlp": dense * 3 * 2.0 * rows * d * cfg["intermediate_size"],
        "shared_expert": sparse * 3 * 2.0 * rows * d * cfg[
            "shared_expert_intermediate_size"],
        "router": sparse * 2.0 * rows * d * cfg["share"]["router_experts"],
        "experts": sparse * expert_matmul_cost(cfg, work)["flops_forward"],
        "head": 2.0 * rows * d * cfg["vocab_size"],
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
    each matmul's operands and result read or written once, in bf16."""
    rows = _held_rows(cfg, work)
    d, f, e = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    fwd = 6.0 * rows * d * f
    weights = 2.0 * e * 3 * d * f
    row_bytes = 2.0 * rows * ((d + 2 * f) + (f + d))
    return {"flops_forward": fwd, "flops_step": 3.0 * fwd,
            "bytes_step": 3.0 * (weights + row_bytes)}


# --------------------------------------------------------------------------
# plain reference (this file's own copy of paddle_tpu/models/
# laguna_reference.py's equations; benchmark/tests holds the two
# together): float32, "highest", the held experts as a loop over a boolean
# mask (what the absent ones would add is left out, as in the program), a
# [T, T] softmax under a mask built densely from positions, computed in
# blocks (one head's rows at a time) so that 64 heads of [4096, 4096]
# scores need not exist at once, both rotaries and YaRN's frequencies
# written out, an untied head.  No auxiliary loss; no document mask in a
# packed sequence.
# --------------------------------------------------------------------------
# One deliberate error each, for the tests: the comparison that decides
# `correct` has to fail on every one on weights where it shows
# (tests/test_laguna_model.py).  What cannot be computed on the program's
# own weights (layer 0 sparse: another parameter list) is a refusal there.
DEPARTURES = (
    "heads_swapped",        # query heads grouped onto the KV heads by the
                            # OTHER kind's count (n // 8 on a full layer,
                            # n // 6, clipped, on a sliding one)
    "window_minus_one",     # 0 <= i - j < window - 1
    "window_plus_one",      # 0 <= i - j < window + 1
    "rope_whole_on_full",   # the full layers turn all of the head
    "plain_freq_on_full",   # theta^(-2i/dim) on the full layers: no YaRN
    "no_attention_factor",  # cos and sin not multiplied by 1.4158883
    "thetas_swapped",       # 10,000 on the full layers, 500,000 on the others
    "gate_per_lane",        # the H gates laid over the H x D lanes (lane j
                            # takes gate j mod H) in place of one a head
    "gate_on_shared",       # no gate in attention; the first gate column
                            # gates the shared expert's output
    "no_gate",              # the sigmoid gate left out
    "softmax_scores",       # the router's scores a softmax over all experts
    "no_renormalisation",   # the chosen scores not divided by their sum
    "no_routed_scale",      # moe_routed_scaling_factor left out
    "no_shared_expert",     # the shared expert left out
)


def reference(cfg, params, batch, departure=None, dtype="float32"):
    """-> (loss, rows [B, T] float32: every token's cross-entropy), on the
    host's CPU device where jax has one: on the chip the reference would
    have to fit beside 11 GB of training state.  `departure` is one of
    DEPARTURES (a wrong model), `dtype` "bfloat16" the stated precision's
    neighbour below (weights, activations, router, gate and matmuls all
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
# the comparison that decides `correct` (kanana2_lm's)
# --------------------------------------------------------------------------
def program_rows():
    """What the program's `is_test` build left in the scope it last ran in
    (loops/train.py compares inside its `scope_guard`): every token's
    cost, [B, T]; None where the scope holds none."""
    import paddle_tpu as fluid
    from paddle_tpu.models import laguna

    rows = fluid.global_scope().find_var(laguna.EVAL_ROWS)
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
    of the absolute differences} (nemotron_h_lm's pair)."""
    if exact_rows is None:
        exact_rows = reference(cfg, params, batch)[1]
    if bf16_rows is None:
        bf16_rows = reference(cfg, params, batch, dtype="bfloat16")[1]
    return {"rms": _rms(bf16_rows, exact_rows),
            "median": _median(bf16_rows, exact_rows)}


def _limits(cfg):
    """LIMITS, or what a rehearsal's data carries in their place (as
    loops/train.py takes its `reference_tolerance`): at 64 lanes the unit
    itself is a few roundings, and a sound program reads up to 0.8 of it.
    The configuration as it is measured has no such key."""
    return cfg.get("reference_limits", LIMITS)


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
    limits = _limits(cfg)
    within = all(found[k] <= limits[k] for k in limits)
    return (loss if within else float("nan")), loss, found


def reference_loss(cfg, params, batch, departure=None, dtype="float32"):
    """The plain reference's loss on these weights and rows, or NaN (see
    `compare`); the readings go to stderr as one JSON line."""
    import json
    import sys

    told, loss, found = compare(cfg, params, batch, departure, dtype)
    if found is not None:
        print("laguna_lm reference: %s" % json.dumps(dict(
            found, limits=_limits(cfg), reference_loss=loss,
            departure=departure,
            dtype=dtype)), file=sys.stderr, flush=True)
    return told


def _rms_norm(x, w, eps):
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_range(rope, dim):
    """(low, high) of YaRN's ramp over a rotary `dim` lanes wide: the pair
    that turns `beta` times over the original positions is dim ln(original
    / (beta 2 pi)) / (2 ln theta); floor of beta_fast's, ceiling of
    beta_slow's, clipped to [0, dim - 1]."""
    def pair(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    return (max(math.floor(pair(rope["beta_fast"])), 0),
            min(math.ceil(pair(rope["beta_slow"])), dim - 1))


def _inv_freq(rope, dim, theta, yarn):
    import jax.numpy as jnp

    i = jnp.arange(dim // 2, dtype=jnp.float32)
    pos = float(theta) ** (2 * i / dim)
    if not yarn:
        return 1.0 / pos
    low, high = yarn_range(dict(rope, rope_theta=theta), dim)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - r) / pos + r / (rope["factor"] * pos)


def _rope(x, rope, head_dim, theta, departure):
    """x [..., T, D]: the first partial_rotary_factor x D lanes turned in
    pairs (i, i + half) at the kind's frequencies, cos and sin times its
    attention_factor; the other lanes as projected."""
    import jax.numpy as jnp

    yarn = rope["rope_type"] == "yarn"
    part = rope.get("partial_rotary_factor", 1)
    if yarn and departure == "rope_whole_on_full":
        part = 1
    dim = int(head_dim * part)
    freq = _inv_freq(rope, dim, theta,
                     yarn and departure != "plain_freq_on_full")
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    amp = rope.get("attention_factor", 1.0)
    if departure == "no_attention_factor":
        amp = 1.0
    cos = (jnp.cos(ang) * amp).astype(x.dtype)
    sin = (jnp.sin(ang) * amp).astype(x.dtype)
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def _loss(m, weights, batch, departure=None):
    import jax
    import jax.numpy as jnp

    d, kv, dh = m["hidden_size"], m["num_key_value_heads"], m["head_dim"]
    eps = m["rms_norm_eps"]
    k, f_moe = m["num_experts_per_tok"], m["moe_intermediate_size"]
    held, offset = m["num_local_experts"], m["expert_offset"]
    ropes = m["rope_parameters"]
    by_kind = dict(zip(m["layer_types"], m["num_attention_heads_per_layer"]))
    it = iter(weights)

    def take(*shape):
        w = next(it)
        if tuple(w.shape) != tuple(shape):
            raise ValueError("reference expected a parameter of shape %s, "
                             "got %s" % (shape, w.shape))
        return w

    def attention(x, i):
        kind, h = m["layer_types"][i], m["num_attention_heads_per_layer"][i]
        wq, wk, wv = take(d, h * dh), take(d, kv * dh), take(d, kv * dh)
        wa = take(d, h) if m["gating"] else None
        wo = take(h * dh, d)
        bsz, t, _ = x.shape
        other = {"sliding_attention": "full_attention",
                 "full_attention": "sliding_attention"}[kind]

        def heads(y, n):  # [B, T, n dh] -> [n, B, T, dh]
            return y.reshape(bsz, t, n, dh).transpose(2, 0, 1, 3)

        q, key, v = heads(x @ wq, h), heads(x @ wk, kv), heads(x @ wv, kv)
        rope = ropes[kind]
        theta = (ropes[other] if departure == "thetas_swapped"
                 else rope)["rope_theta"]
        q = _rope(q, rope, dh, theta, departure)
        key = _rope(key, rope, dh, theta, departure)
        window = 0
        if kind == "sliding_attention":
            window = m["sliding_window"] + {
                "window_minus_one": -1, "window_plus_one": 1}.get(departure, 0)
        dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
        keep = dist >= 0
        if window:
            keep = keep & (dist < window)

        def head(qkv):  # one head's rows at a time
            qh, kh, vh = qkv
            s = (jnp.einsum("bqd,bkd->bqk", qh, kh) * dh ** -0.5).astype(
                jnp.float32)
            s = jnp.where(keep, s, -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd",
                              jax.nn.softmax(s, -1).astype(qh.dtype), vh)

        # query head n reads kv head n // (h / kv)
        group = h // kv
        if departure == "heads_swapped" and other in by_kind:
            group = by_kind[other] // kv
        served = jnp.minimum(jnp.arange(h) // group, kv - 1)
        ctx = jax.lax.map(head, (q, key[served], v[served]))  # [H, B, T, dh]
        ctx = ctx.transpose(1, 2, 0, 3)                       # [B, T, H, dh]
        if wa is not None and departure not in ("no_gate", "gate_on_shared"):
            a = jax.nn.sigmoid((x @ wa).astype(jnp.float32)).astype(x.dtype)
            if departure == "gate_per_lane":
                ctx = ctx.reshape(bsz, t, h * dh) * jnp.tile(a, dh)
            else:
                ctx = ctx * a[..., None]
        return ctx.reshape(bsz, t, h * dh) @ wo, wa

    def mlp(x, f):
        w1, w3, w2 = take(d, f), take(d, f), take(f, d)
        return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2

    def routed(x):
        router = take(d, m["num_experts"])
        gate_up, down = take(held, d, 2 * f_moe), take(held, f_moe, d)
        x2 = x.reshape(-1, d)
        logits = (x2 @ router).astype(jnp.float32)
        s = (jax.nn.softmax(logits, -1) if departure == "softmax_scores"
             else jax.nn.sigmoid(logits))
        top_p, top_e = jax.lax.top_k(s, k)
        if departure != "no_renormalisation":
            top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
        if departure != "no_routed_scale":
            top_p = top_p * m["moe_routed_scaling_factor"]
        top_p = top_p.astype(x.dtype)
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
        a, wa = attention(hidden, i)
        x = x + a
        hidden2 = _rms_norm(x, take(d), eps)
        if m["mlp_layer_types"][i] == "dense":
            y = mlp(hidden2, m["intermediate_size"])
        else:
            y = routed(hidden2)
            if m["shared_expert_intermediate_size"]:
                shared = mlp(hidden2, m["shared_expert_intermediate_size"])
                if departure == "gate_on_shared" and wa is not None:
                    shared = shared * jax.nn.sigmoid(hidden2 @ wa[:, :1])
                if departure != "no_shared_expert":
                    y = y + shared
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
