"""Nemotron-3-Nano-30B-A3B's forward pass and loss in plain float32
jax.numpy: the reference `models/nemotron_h.py` (through Executor.run) is
tested against.  No import from the code under test; no kernel, no chunk,
no sort, no grouped matmul, no cache: the Mamba-2 scan is the
token-by-token recurrence in a `lax.scan` over T, the convolution four
shifted products, the attention an explicit [T, T] softmax under a mask
built densely, the experts a loop over a boolean mask, gradients jax.grad.

    x = Emb[ids]
    for layer i, kind = hybrid_override_pattern[i]:  x += Block_kind(rms(x))
    logits = rms(x) @ W_head
    rms(x; w) = x rsqrt(mean x^2 + layer_norm_epsilon) w

  M   [z | xBC | dt] = h W_in (4096 | 4096 + 2 x 8 x 128 | 64 wide as
      published); xBC = silu(conv(xBC) + b): depthwise causal, one L-tap
      filter a channel, zeros left of t = 0; [x | B | C] = xBC: x [T, H, P],
      B, C [T, G, N], head j reads group j // (H / G);
      dt = softplus(dt + dt_bias[j]) [T, H]; A_j = -exp(A_log[j]);
      per head, S_0 = 0 [P, N]:
          S_t = exp(dt_t A_j) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D_j x_t
      y = y silu(z), then y rsqrt(mean over each group of H P / G channels
      of y^2 + eps) gain; M = y W_out.
  *   q = h W_q [T, H, dh], k = h W_k, v = h W_v [T, Hkv, dh]; no norm, no
      rotary, no gate; o = softmax(q k^T dh^-0.5, causal) v, query head j
      reads KV head j // (H / Hkv); * = concat(o) W_o.
  E   s = sigmoid(h W_r); chosen = top-k of s + b (b without gradient); w =
      routed_scaling_factor s[chosen] / (sum + 1e-20); relu(h W_up)^2 W_down
      of the shared expert + sum over the chosen experts THIS share holds of
      w_e relu(h W_up_e)^2 W_down_e.

Departures from the published model, each on purpose:
- a packed sequence carries no document mask;
- the grouped norm's gain is one [G, H P / G] array (the published [H P],
  the same numbers);
- a chip's share: given `num_local_experts` < `n_routed_experts` the mixture
  holds experts [expert_offset, expert_offset + num_local_experts) of the
  ones its router chooses among and leaves out what the others would add,
  as the program does; the shared expert is whole on every share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer pre_norm [d], then for M: W_in [d, 2 H P + 2 G N + H], the filter
[H P + 2 G N, L], its bias [H P + 2 G N], dt_bias [H], A_log [H], D [H], the
gated norm's gain [G, H P / G], W_out [H P, d]; for *: W_q [d, H dh], W_k,
W_v [d, Hkv dh], W_o [H dh, d]; for E: router [d, E], its selection bias
[E], up [E_held, d, f], down [E_held, f, d], the shared expert's up
[d, fs] and down [fs, d]; final_norm [d]; head [d, V].
"""

import jax
import jax.numpy as jnp

KINDS = {"M": "mamba2", "E": "experts", "*": "attention"}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def causal_conv_silu(x, filt, bias):
    """x [B, T, C], filt [C, L], bias [C]:
    silu(sum_j filt[:, j] x_{t-(L-1)+j} + bias)."""
    taps, t = filt.shape[1], x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(
        sum(xp[:, j:j + t] * filt[:, j] for j in range(taps)) + bias)


def selective_scan(x, dt, a, b, c, d):
    """x [B, T, H, P], dt [B, T, H], a [H] (negative), b, c [B, T, H, N]
    (already one a head), d [H] -> y [B, T, H, P]: the recurrence, one
    token a step."""
    def step(s, v):
        xt, dtt, bt, ct = v
        s = (jnp.exp(dtt * a)[..., None, None] * s
             + (dtt[..., None] * xt)[..., None] * bt[..., None, :])
        return s, jnp.einsum("bhpn,bhn->bhp", s, ct) + d[:, None] * xt

    xs = [jnp.moveaxis(v, 1, 0) for v in (x, dt, b, c)]
    _, y = jax.lax.scan(
        step, jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[-1:]), xs)
    return jnp.moveaxis(y, 0, 1)


def mamba2(cfg, x, w_in, filt, conv_bias, dt_bias, a_log, skip, gain, w_out):
    bsz, t, _ = x.shape
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n = cfg["n_groups"], cfg["ssm_state_size"]
    inner = h * p
    zxbcdt = x @ w_in
    z, xbc, dt = (zxbcdt[..., :inner], zxbcdt[..., inner:-h],
                  zxbcdt[..., -h:])
    xbc = causal_conv_silu(xbc, filt, conv_bias)
    xs = xbc[..., :inner].reshape(bsz, t, h, p)
    b = xbc[..., inner:inner + g * n].reshape(bsz, t, g, n)
    c = xbc[..., inner + g * n:].reshape(bsz, t, g, n)
    b, c = (jnp.repeat(v, h // g, axis=2) for v in (b, c))
    y = selective_scan(xs, jax.nn.softplus(dt + dt_bias), -jnp.exp(a_log),
                       b, c, skip)
    y = (y.reshape(bsz, t, inner) * jax.nn.silu(z)).reshape(
        bsz, t, g, inner // g)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                          + cfg["layer_norm_epsilon"]) * gain
    return y.reshape(bsz, t, inner) @ w_out


def attention(cfg, x, wq, wk, wv, wo):
    b, t, _ = x.shape
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])

    def heads(y, count):
        return y.reshape(b, t, count, dh).transpose(0, 2, 1, 3)

    q = heads(x @ wq, h)
    k, v = (jnp.repeat(heads(x @ w, hkv), h // hkv, axis=1)
            for w in (wk, wv))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    s = jnp.where(jnp.arange(t)[:, None] >= jnp.arange(t)[None, :], s,
                  -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, h * dh) @ wo


def relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


def routed(cfg, x, router, bias, up, down):
    """-> (y, chosen experts [N, k]).  up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_tok"]
    offset = int(cfg.get("expert_offset", 0))
    x2 = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(x2 @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    top_p = jnp.take_along_axis(s, top_e, -1)
    if cfg.get("norm_topk_prob", True):
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg["routed_scaling_factor"]
    y = jnp.zeros_like(x2)
    for local in range(up.shape[0]):
        chosen = top_e == offset + local  # [N, k]
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        out = relu2_mlp(x2, up[local], down[local])
        y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out, 0.0)
    return y.reshape(x.shape), top_e


def kinds_of(cfg):
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern %r is not %d layers"
                         % (pattern, cfg["num_hidden_layers"]))
    return [KINDS[ch] for ch in pattern]


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, [per expert layer chosen experts])."""
    eps = cfg["layer_norm_epsilon"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    x, chosen = next(it)[ids], []
    for kind in kinds_of(cfg):
        h = rms_norm(x, next(it), eps)
        if kind == "mamba2":
            x = x + mamba2(cfg, h, *take(8))
        elif kind == "attention":
            x = x + attention(cfg, h, *take(4))
        else:
            y, top_e = routed(cfg, h, *take(4))
            if cfg.get("n_shared_experts", 1):
                y = y + relu2_mlp(h, *take(2))
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
