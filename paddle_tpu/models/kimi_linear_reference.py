"""Kimi-Linear-48B-A3B's forward pass and loss in plain float32 jax.numpy:
the reference `models/kimi_linear.py` (through Executor.run) is tested
against.  No import from the code under test; no kernel, no chunk, no
triangular solve, no sort, no grouped matmul, no cache: Kimi Delta
Attention is the token-by-token recurrence in a `lax.scan` over T, the
convolution four shifted products, latent attention an explicit [T, T]
softmax under a mask built densely, the experts a loop over a boolean
mask, gradients jax.grad.

    x = Emb[ids]
    for layer i:  x += Mixer_i(rms(x)); x += F_i(rms(x))
    logits = rms(x) @ W_head

  KDA   q = l2norm(silu(conv(h W_q))), k likewise, v = silu(conv(h W_v)),
        each [T, H, dh]; conv: depthwise causal, one L-tap filter a
        channel, zeros left of t = 0; l2norm over dh, x rsqrt(sum x^2 +
        1e-6);
        g = -exp(A_log[head]) softplus((h W_fa) W_fb + dt_bias) [T, H, dh];
        beta = sigmoid(h W_b) [T, H];
        per head, S_0 = 0 [dh, dh]:
            S_t = (I - beta_t k_t k_t^T) Diag(exp(g_t)) S_{t-1}
                  + beta_t k_t v_t^T
            o_t = S_t^T q_t dh^-0.5
        KDA = (rms(o; gain [dh]) * sigmoid((h W_ga) W_gb)) W_o.
  MLA   kanana-2's latent attention WITHOUT rotary (`mla_use_nope`):
        q = h W_q -> [H, nope + rope]; [c, k_s] = h W_kva -> [r], [rope];
        [k_n, v] = rms(c; own gain) W_kvb -> [H, nope], [H, dv];
        o = softmax(q [k_n, k_s]^T (nope + rope)^-0.5, causal) v (k_s ONE
        for all heads); MLA = concat(o) W_o.
  F_i   i < first_k_dense_replace: (silu(h W1) * h W3) W2; else
        Routed(h) + Shared(h): Routed: s = sigmoid(h W_r); chosen = top-k
        of s + b; w = s[chosen] / (sum + 1e-20) * routed_scaling_factor;
        sum over the chosen experts THIS share holds of w_e SwiGLU_e(h).

Departures from the published model, each on purpose:
- `e_score_correction_bias` is an input like any weight, without gradient;
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix;
- a chip's share: given `num_local_experts` < `num_experts` the mixture
  holds experts [expert_offset, expert_offset + num_local_experts) of the
  ones its router chooses among and leaves out what the others would add,
  as the program does; the shared expert is whole on every share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer attn_norm [d], then for a KDA layer W_q, W_k, W_v [d, H dh], W_fa
[d, dh], W_fb [dh, H dh], dt_bias [H dh], W_ga [d, dh], W_gb [dh, H dh],
W_b [d, H], the q, k, v filters [H dh, L], A_log [H, 1], o_norm [dh], W_o
[H dh, d], for an MLA layer W_q [d, H (nope + rope)], W_kva [d, r + rope],
kv_a_norm [r], W_kvb [r, H (nope + dv)], W_o [H dv, d]; ffn_norm [d]; then
for a dense layer w1 (gate) [d, f], w3 (up) [d, f], w2 [f, d], for an
expert layer router [d, E], bias [E], gate_up [E_held, d, 2 f_e], down
[E_held, f_e, d], shared w1 [d, n_s f_e], w3, w2 [n_s f_e, d]; final_norm
[d]; head [d, V].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv_silu(x, filt):
    """x [B, T, C], filt [C, L]: silu(sum_j filt[:, j] x_{t-(L-1)+j})."""
    taps, t = filt.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(xp[:, j:j + t] * filt[:, j] for j in range(taps)))


def delta_rule(q, k, v, g, beta):
    """q, k, g [B, T, H, dk], v [B, T, H, dv], beta [B, T, H] -> o
    [B, T, H, dv]: the recurrence, one token a step."""
    b, _, h, dk = q.shape

    def step(s, x):
        qt, kt, vt, gt, bt = x
        s = jnp.exp(gt)[..., None] * s
        u = bt[..., None] * (vt - jnp.einsum("bhc,bhcv->bhv", kt, s))
        s = s + kt[..., None] * u[..., None, :]
        return s, jnp.einsum("bhc,bhcv->bhv", qt, s)

    xs = [jnp.moveaxis(a, 1, 0) for a in (q * dk ** -0.5, k, v, g, beta)]
    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1])), xs)
    return jnp.moveaxis(o, 0, 1)


def kda(cfg, x, wq, wk, wv, wfa, wfb, dt_bias, wga, wgb, wb, fq, fk, fv,
        a_log, o_norm, wo):
    b, t, _ = x.shape
    la = cfg["linear_attn_config"]
    h, dh = la["num_heads"], la["head_dim"]

    def heads(y):
        return y.reshape(b, t, h, dh)

    def l2norm(y):
        return y * jax.lax.rsqrt((y * y).sum(-1, keepdims=True) + 1e-6)

    q = l2norm(heads(causal_conv_silu(x @ wq, fq)))
    k = l2norm(heads(causal_conv_silu(x @ wk, fk)))
    v = heads(causal_conv_silu(x @ wv, fv))
    g = -jnp.exp(a_log.reshape(h, 1)) * heads(
        jax.nn.softplus((x @ wfa) @ wfb + dt_bias))
    beta = jax.nn.sigmoid(x @ wb)
    o = rms_norm(delta_rule(q, k, v, g, beta), o_norm, cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(heads((x @ wga) @ wgb))
    return o.reshape(b, t, h * dh) @ wo


def latent_attention(cfg, x, wq, wkva, kv_norm, wkvb, wo):
    b, t, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    if not cfg["mla_use_nope"]:
        raise NotImplementedError("the reference is the published model's: "
                                  "latent attention without rotary")
    q = (x @ wq).reshape(b, t, h, nope + rot).transpose(0, 2, 1, 3)
    latent = x @ wkva
    c, k_s = latent[..., :r], latent[..., r:]
    kv = (rms_norm(c, kv_norm, cfg["rms_norm_eps"]) @ wkvb).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_s[:, None], (b, h, t, rot))], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (nope + rot) ** -0.5
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], s,
                  -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1),
                     kv[..., nope:])
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ wo


def swiglu_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed(cfg, x, router, bias, gate_up, down):
    """-> (y, chosen experts [N, k]).  gate_up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_token"]
    offset, f = int(cfg.get("expert_offset", 0)), down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(x2 @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    top_p = jnp.take_along_axis(s, top_e, -1)
    if cfg.get("moe_renormalize", True):
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(x2)
    for local in range(gate_up.shape[0]):
        chosen = top_e == offset + local  # [N, k]
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        gu = x2 @ gate_up[local]
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down[local]
        y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out, 0.0)
    return y.reshape(x.shape), top_e


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, [per expert layer chosen experts])."""
    eps, la = cfg["rms_norm_eps"], cfg["linear_attn_config"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    x, chosen = next(it)[ids], []
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, next(it), eps)
        if i + 1 in la["kda_layers"]:
            x = x + kda(cfg, h, *take(15))
        elif i + 1 in la["full_attn_layers"]:
            x = x + latent_attention(cfg, h, *take(5))
        else:
            raise ValueError("layer %d has no mixer" % (i + 1))
        h = rms_norm(x, next(it), eps)
        if i < cfg["first_k_dense_replace"]:
            x = x + swiglu_mlp(h, *take(3))
        else:
            y, top_e = routed(cfg, h, *take(4))
            if cfg["num_shared_experts"]:
                y = y + swiglu_mlp(h, *take(3))
            x = x + y
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
