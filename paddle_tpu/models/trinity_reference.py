"""Trinity-Mini's forward pass and loss in plain float32 jax.numpy: the
reference `models/trinity.py` (through Executor.run) is tested against.
No import from the code under test; no kernel, no sort, no grouped matmul,
no cache: attention is an explicit [T, T] softmax under a mask built
densely from positions, the experts are a loop over a boolean mask, RoPE
is written out on the (i, i + D/2) pairs, gradients are jax.grad.

    x = Emb[ids] * sqrt(d)                                  (mup_enabled)
    for layer i:  x += rms(Attn_i(rms(x)));  x += rms(F_i(rms(x)))
    logits = rms(x) @ W_head

  Attn_i  q = h W_q -> [H, D]; k = h W_k, v = h W_v -> [H_kv, D]; gate =
          h W_g -> [H D]; q, k = rms over D of every head (one [D] gain
          for q's heads, one for k's);
          sliding_attention: q, k = rope(q), rope(k) (theta, all D,
          rotate-half); key j visible to query i iff 0 <= i - j < window;
          full_attention: no position encoding; iff 0 <= i - j;
          a = softmax(q k^T D^-0.5 over the visible keys) v, each kv head
          serving H / H_kv consecutive query heads;
          Attn = (concat(a) * sigmoid(gate)) W_o.
  F_i     i < num_dense_layers: (silu(h W1) * h W3) W2; else
          Routed(h) + Shared(h): s = sigmoid(h W_r); chosen = top-k of s +
          b; w = route_scale * s[chosen] / (sum + 1e-20) (route_norm);
          the sum over the chosen experts THIS share holds of w_e
          SwiGLU_e(h); Shared the same MLP at num_shared_experts x f_e.

Departures from the published model, each on purpose:
- `expert_bias` is an input like any weight, without gradient, as in the
  published code (what a training program does to it between steps,
  `expert_bias_update`, is no part of a loss);
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix (gate in
  the first f columns): the same numbers, stored side by side;
- a chip's share: given fewer expert matrices than the router is wide the
  mixture holds experts [expert_offset, expert_offset + their count) and
  leaves out what the others would add, as the program does; the shared
  expert is whole on every share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer input_norm [d], W_q [d, H D], W_k [d, H_kv D], W_v, W_g [d, H D],
q_norm [D], k_norm [D], W_o [H D, d], post_attn_norm [d], pre_mlp_norm
[d]; then for a dense layer w1 (gate) [d, f], w3 (up), w2 [f, d], for an
expert layer router [d, E], bias [E], gate_up [E_held, d, 2 f_e], down
[E_held, f_e, d], shared w1 [d, n_s f_e], w3, w2; post_mlp_norm [d];
final_norm [d]; head [d, V].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [..., T, D]: the pair (x[i], x[i + D/2]) turned by t
    theta^(-2i/D)."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def visible(t, window):
    """[T, T] bool: key j (columns) visible to query i (rows); window 0 is
    plain causal."""
    dist = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    keep = dist >= 0
    return keep & (dist < window) if window else keep


def attention(cfg, kind, x, wq, wk, wv, wg, q_norm, k_norm, wo):
    b, t, _ = x.shape
    h, kv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    sliding = kind == "sliding_attention"

    def heads(y, n):  # [B, T, n D] -> [B, n, T, D]
        return y.reshape(b, t, n, d).transpose(0, 2, 1, 3)

    q = rms_norm(heads(x @ wq, h), q_norm, eps)
    k = rms_norm(heads(x @ wk, kv), k_norm, eps)
    v = heads(x @ wv, kv)
    if sliding:
        theta = float(cfg["rope_theta"])
        q, k = rope(q, theta), rope(k, theta)
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
    s = jnp.where(visible(t, cfg["sliding_window"] if sliding else 0), s,
                  -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, t, h * d)
    return (ctx * jax.nn.sigmoid(x @ wg)) @ wo


def swiglu_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def routed(cfg, x, router, bias, gate_up, down):
    """-> (y, chosen experts [N, k]).  gate_up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_tok"]
    offset, f = int(cfg.get("expert_offset", 0)), down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(x2 @ router)
    _, top_e = jax.lax.top_k(s + jax.lax.stop_gradient(bias), k)
    top_p = jnp.take_along_axis(s, top_e, -1)
    if cfg.get("route_norm", True):
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-20)
    top_p = top_p * cfg["route_scale"]
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
    eps = cfg["rms_norm_eps"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    x, chosen = next(it)[ids], []
    if cfg.get("mup_enabled", True):
        x = x * cfg["hidden_size"] ** 0.5
    for i in range(cfg["num_hidden_layers"]):
        h = rms_norm(x, next(it), eps)
        a = attention(cfg, cfg["layer_types"][i], h, *take(7))
        x = x + rms_norm(a, next(it), eps)
        h = rms_norm(x, next(it), eps)
        if i < cfg["num_dense_layers"]:
            f = swiglu_mlp(h, *take(3))
        else:
            f, top_e = routed(cfg, h, *take(4))
            if cfg["num_shared_experts"]:
                f = f + swiglu_mlp(h, *take(3))
            chosen.append(top_e)
        x = x + rms_norm(f, next(it), eps)
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
