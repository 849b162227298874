"""Laguna-XS.2's forward pass and loss in plain float32 jax.numpy: the
reference `models/laguna.py` (through Executor.run) is tested against.  No
import from the code under test; no kernel, no sort, no grouped matmul, no
cache: attention is an explicit [T, T] softmax under a mask built densely
from positions, the experts are a loop over a boolean mask, both rotaries
and YaRN's frequencies are written out, gradients are jax.grad.

    x = Emb[ids]
    for layer l:  x += Attn_l(rms(x));  x += F_l(rms(x))
    logits = rms(x) @ W_head

  Attn_l  H = num_attention_heads_per_layer[l], G = num_key_value_heads,
          D = head_dim.  q = h W_q -> [H, D]; k = h W_k, v = h W_v ->
          [G, D]; a = sigmoid(h W_a) -> [H].  q, k = R(q), R(k) with R the
          kind's rotary (`rope_parameters[kind]`): over the head's first
          partial_rotary_factor x D lanes, pairs (i, i + half), the other
          lanes as projected; inverse frequencies theta^(-2i/dim), or
          under `rope_type` "yarn" the blend `inv_freq` writes out, and
          then cos and sin times `attention_factor`.
          sliding_attention: key j visible to query i iff 0 <= i - j <
          sliding_window; full_attention: iff 0 <= i - j.
          o = softmax(q k^T D^-0.5 over the visible keys) v, query head n
          reading KV head n // (H / G); Attn = concat_n(a_n o_n) W_o.
  F_l     dense: (silu(h W1) * h W3) W2.  sparse: Routed(h) + Shared(h): s =
          sigmoid(h W_r); chosen = top-k of s; w = scale * s[chosen] / (sum
          + 1e-20); the sum over the chosen experts THIS share holds of w_e
          SwiGLU_e(h); Shared the same MLP at its own width.

Departures from the published model, each on purpose:
- the published modeling code was not at hand: the gate's form (one a
  head), the router's score (sigmoid, renormalised, scaled), no QK-norm,
  two norms a layer and SiLU are readings of the config's keys and its
  published parameter count (benchmark/configs/laguna_xs2_33b_a3b.json,
  `assumed`);
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix (gate in
  the first f columns): the same numbers, stored side by side;
- a chip's share: given fewer expert matrices than the router is wide the
  mixture holds experts [expert_offset, expert_offset + their count) and
  leaves out what the others would add, as the program does; the shared
  expert is whole on every share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer input_norm [d], W_q [d, H D], W_k [d, G D], W_v, W_a [d, H], W_o
[H D, d], pre_mlp_norm [d]; then for a dense layer w1 (gate) [d, f], w3
(up), w2 [f, d], for a sparse layer router [d, E], gate_up [E_held, d,
2 f_e], down [E_held, f_e, d], shared w1 [d, f_s], w3, w2; final_norm [d];
head [d, V].
"""

import math

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def yarn_range(rope, dim):
    """(low, high): the pair that turns `beta` times over the original
    positions is dim ln(original / (beta 2 pi)) / (2 ln theta)."""
    def pair(turns):
        return (dim * math.log(rope["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    return (max(math.floor(pair(rope["beta_fast"])), 0),
            min(math.ceil(pair(rope["beta_slow"])), dim - 1))


def inv_freq(rope, dim):
    """[dim / 2] inverse frequencies of a rotary `dim` lanes wide."""
    i = jnp.arange(dim // 2, dtype=jnp.float32)
    pos = float(rope["rope_theta"]) ** (2 * i / dim)
    if rope["rope_type"] == "default":
        return 1.0 / pos
    low, high = yarn_range(rope, dim)
    r = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return (1.0 - r) / pos + r / (rope["factor"] * pos)


def rope(x, params, head_dim):
    """x [..., T, D]: the first partial_rotary_factor x D lanes turned in
    pairs (i, i + half), cos and sin times attention_factor."""
    dim = int(head_dim * params.get("partial_rotary_factor", 1))
    ang = (jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None]
           * inv_freq(params, dim)[None])
    amp = params.get("attention_factor", 1.0)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    a, b, rest = x[..., :dim // 2], x[..., dim // 2:dim], x[..., dim:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos, rest], -1)


def visible(t, window):
    """[T, T] bool: key j (columns) visible to query i (rows); window 0 is
    plain causal."""
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = dist >= 0
    return keep & (dist < window) if window else keep


def attention(cfg, i, x, wq, wk, wv, wa, wo):
    b, t, _ = x.shape
    h, kv, d = (cfg["num_attention_heads_per_layer"][i],
                cfg["num_key_value_heads"], cfg["head_dim"])
    kind = cfg["layer_types"][i]
    turn = cfg["rope_parameters"][kind]

    def heads(y, n):  # [B, T, n D] -> [B, n, T, D]
        return y.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    q, k, v = heads(x @ wq, h), heads(x @ wk, kv), heads(x @ wv, kv)
    q, k = rope(q, turn, d), rope(k, turn, d)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    window = cfg["sliding_window"] if kind == "sliding_attention" else 0
    s = jnp.where(visible(t, window), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    ctx = ctx.transpose(0, 2, 1, 3)  # [B, T, H, D]
    if cfg.get("gating", True):
        ctx = ctx * jax.nn.sigmoid(x @ wa)[..., None]
    return ctx.reshape(b, t, h * d) @ wo


def swiglu_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed(cfg, x, router, gate_up, down):
    """-> (y, chosen experts [N, k]).  gate_up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_tok"]
    offset, f = int(cfg.get("expert_offset", 0)), down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(x2 @ router)
    top_p, top_e = jax.lax.top_k(s, k)
    top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg["moe_routed_scaling_factor"]
    y = jnp.zeros_like(x2)
    for local in range(gate_up.shape[0]):
        chosen = top_e == offset + local  # [N, k]
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        gu = x2 @ gate_up[local]
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down[local]
        y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out, 0.0)
    return y.reshape(x.shape), top_e


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, [per sparse layer chosen experts])."""
    eps = cfg["rms_norm_eps"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    x, chosen = next(it)[ids], []
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, next(it), eps)
        wq, wk, wv = take(3)
        wa = next(it) if cfg.get("gating", True) else None
        x = x + attention(cfg, i, h, wq, wk, wv, wa, next(it))
        h = rms_norm(x, next(it), eps)
        if cfg["mlp_layer_types"][i] == "dense":
            f = swiglu_mlp(h, *take(3))
        else:
            f, top_e = routed(cfg, h, *take(3))
            if cfg["shared_expert_intermediate_size"]:
                f = f + swiglu_mlp(h, *take(3))
            chosen.append(top_e)
        x = x + f
    logits = rms_norm(x, next(it), eps) @ next(it)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    return logits, chosen


def token_costs(cfg, params, batch):
    """[B, T]: every token's cross-entropy."""
    logits, _ = forward(cfg, params, jnp.asarray(batch["ids"]))
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    return lse - picked


def loss(cfg, params, batch):
    """Weighted token cross-entropy."""
    w = jnp.asarray(batch["loss_weight"], jnp.float32)
    return (token_costs(cfg, params, batch) * w).sum() / w.sum()


def loss_and_grads(cfg, params, batch):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
