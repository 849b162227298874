"""Qwen3-Next-80B-A3B's forward pass and loss in plain float32 jax.numpy:
the reference `models/qwen3_next.py` (through Executor.run) is tested
against.  No import from the code under test; no kernel, no chunk, no
inverse, no sort, no grouped matmul, no cache: Gated DeltaNet is the
token-by-token recurrence in a `lax.scan` over T, the convolution four
shifted products, the attention an explicit [T, T] softmax under a mask
built densely, rotary written out, the experts a loop over a boolean mask,
gradients jax.grad.

    x = Emb[ids]
    for layer i (1-based):  x += Mixer_i(rms(x)); x += F(rms(x))
    logits = rms(x) @ W_head
    rms(x; w) = x rsqrt(mean x^2 + eps) (1 + w)

  GDN   (i % full_attention_interval != 0)
        [q | k | v] = silu(conv(h W_qkv)): q, k [T, Hk, dk], v [T, Hv, dv];
        conv: depthwise causal, one L-tap filter a channel over the
        concatenated channels, zeros left of t = 0;
        q = l2norm(q) dk^-0.5, k = l2norm(k) (x rsqrt(sum x^2 + 1e-6) over
        dk); value head j reads key head j // (Hv / Hk);
        beta = sigmoid(h W_b) [T, Hv];
        g = -exp(A_log[j]) softplus(h W_a + dt_bias[j]) [T, Hv];
        per value head, S_0 = 0 [dk, dv]:
            S_t = (I - beta_t k_t k_t^T) exp(g_t) S_{t-1} + beta_t k_t v_t^T
            o_t = S_t^T q_t
        GDN = (o rsqrt(mean o^2 + eps) gain [dv] * silu(h W_z)) W_o.
  Attn  q = h W_q [T, H, dh], gate = h W_g, k = h W_k, v = h W_v
        [T, Hkv, dh]; q, k: rms over dh with the 1 + w gain (one weight
        for q's heads, one for k's), then rotate-half rotary with theta on
        the first dh * partial_rotary_factor lanes (the pair (i, i + r/2)
        by t theta^(-2i/r), r the rotated width), the others untouched;
        o = softmax(q k^T dh^-0.5, causal) v, query head j reads KV head
        j // (H / Hkv); Attn = (concat(o) * sigmoid(gate)) W_o.
  F     sigmoid(h w_sg) (silu(h W1) * h W3) W2 + Routed(h); Routed: p =
        softmax(h W_r); chosen = top-k of p; w = p[chosen] / sum; sum over
        the chosen experts THIS share holds of w_e SwiGLU_e(h).

Departures from the published model, each on purpose:
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix; the
  published q_proj's query and gate halves are two matrices, the
  published in_proj_qkvz / in_proj_ba four (the same numbers);
- a chip's share: given `num_local_experts` < `num_experts` the mixture
  holds experts [expert_offset, expert_offset + num_local_experts) of the
  ones its router chooses among and leaves out what the others would add,
  as the program does; the shared expert is whole on every share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer attn_norm [d], then for a GDN layer W_qkv [d, 2 Hk dk + Hv dv], W_z
[d, Hv dv], W_b [d, Hv], W_a [d, Hv], dt_bias [Hv], the filter [2 Hk dk +
Hv dv, L], A_log [Hv], o_norm [dv], W_o [Hv dv, d], for an attention layer
W_q [d, H dh], W_k, W_v [d, Hkv dh], W_g [d, H dh], q_norm [dh], k_norm
[dh], W_o [H dh, d]; ffn_norm [d]; router [d, E], gate_up [E_held, d, 2 f],
down [E_held, f, d], shared w1 (gate) [d, fs], w3 (up) [d, fs], w2 [fs, d],
the shared expert's gate [d, 1]; final_norm [d]; head [d, V].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    """The model's norm: the gain is 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + w)


def causal_conv_silu(x, filt):
    """x [B, T, C], filt [C, L]: silu(sum_j filt[:, j] x_{t-(L-1)+j})."""
    taps, t = filt.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + t] * filt[:, j] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """q, k [B, T, H, dk] (already one a value head), v [B, T, H, dv], g,
    beta [B, T, H] -> o [B, T, H, dv]: the recurrence, one token a step,
    one decay a head."""
    b, _, h, dk = q.shape

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None, None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt, s)

    xs = [jnp.moveaxis(a, 1, 0) for a in (q * dk ** -0.5, k, v, g, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def gdn(cfg, x, wqkv, wz, wb, wa, dt_bias, filt, a_log, o_norm, wo):
    b, t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]

    def l2norm(y):
        return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    qkv = causal_conv_silu(x @ wqkv, filt)
    q = l2norm(qkv[..., :hk * dk].reshape(b, t, hk, dk))
    k = l2norm(qkv[..., hk * dk:2 * hk * dk].reshape(b, t, hk, dk))
    v = qkv[..., 2 * hk * dk:].reshape(b, t, hv, dv)
    q, k = (jnp.repeat(y, hv // hk, axis=2) for y in (q, k))
    beta = jax.nn.sigmoid(x @ wb)
    g = -jnp.exp(a_log) * jax.nn.softplus(x @ wa + dt_bias)
    o = delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg["rms_norm_eps"]) * o_norm
    o = o * jax.nn.silu((x @ wz).reshape(b, t, hv, dv))
    return o.reshape(b, t, hv * dv) @ wo


def rotate_part(x, theta, width):
    """x [B, H, T, dh]: rotate-half rotary on lanes [0, width), the pair
    (i, i + width / 2) by t theta^(-2i / width); the other lanes as they
    are."""
    t, half = x.shape[2], width // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / width)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:width]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., width:]], -1)


def gated_attention(cfg, x, wq, wk, wv, wg, q_norm, k_norm, wo):
    b, t, _ = x.shape
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    width = int(dh * cfg["partial_rotary_factor"])

    def heads(y, n, w=None):
        y = y.reshape(b, t, n, dh)
        if w is not None:
            y = rms_norm(y, w, eps)
        return y.transpose(0, 2, 1, 3)

    q = rotate_part(heads(x @ wq, h, q_norm), theta, width)
    k = rotate_part(heads(x @ wk, hkv, k_norm), theta, width)
    v = heads(x @ wv, hkv)
    k, v = (jnp.repeat(y, h // hkv, axis=1) for y in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], s,
                  -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h * dh)
    return (ctx * jax.nn.sigmoid(x @ wg)) @ wo


def swiglu_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed(cfg, x, router, gate_up, down):
    """-> (y, chosen experts [N, k]).  gate_up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_tok"]
    offset, f = int(cfg.get("expert_offset", 0)), down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    p = jax.nn.softmax(x2 @ router, -1)
    top_p, top_e = jax.lax.top_k(p, k)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x2)
    for local in range(gate_up.shape[0]):
        chosen = top_e == offset + local  # [N, k]
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        gu = x2 @ gate_up[local]
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down[local]
        y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out, 0.0)
    return y.reshape(x.shape), top_e


def shared_expert(x, w1, w3, w2, wsg):
    """The shared expert behind its own gate, a number a token."""
    return jax.nn.sigmoid(x @ wsg) * swiglu_mlp(x, w1, w3, w2)


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, [per layer chosen experts])."""
    eps = cfg["rms_norm_eps"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    x, chosen = next(it)[ids], []
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, next(it), eps)
        if (i + 1) % cfg["full_attention_interval"]:
            x = x + gdn(cfg, h, *take(9))
        else:
            x = x + gated_attention(cfg, h, *take(7))
        h = rms_norm(x, next(it), eps)
        y, top_e = routed(cfg, h, *take(3))
        x = x + y + shared_expert(h, *take(4))
        chosen.append(top_e)
    logits = rms_norm(x, next(it), eps) @ next(it)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    return logits, chosen


def token_costs(cfg, params, batch):
    """[B, T] every token's cross-entropy."""
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
