"""LFM2-MoE's forward pass and loss in plain float32 jax.numpy: the
reference `models/lfm2.py` (through Executor.run) is tested against.  No
import from the code under test; no kernel, no sort, no grouped matmul:
the convolution is L shifted adds, the experts are a loop over a boolean
mask, attention is a full [T, T] softmax under a tril mask with each
key/value head repeated for its query heads, RoPE is rotate-half over the
whole head, gradients are jax.grad.

    x = Emb[ids]
    for layer i:  x += Op_i(rms(x)); x += Ffn_i(rms(x))
    logits = rms(x) @ Emb^T

Departures from the published model, each on purpose:
- the head is tied to the embedding (the family ties; config.json has no
  key for it);
- `expert_bias` is an input like any weight, without gradient, as in the
  published code (what a training program does to it between steps,
  `expert_bias_update`, is no part of a loss), and there is no auxiliary
  loss (the config has no coefficient for one);
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix (gate in
  the first f columns): the same numbers, stored side by side;
- a chip's share: given `num_local_experts` < `num_experts` the mixture
  holds experts [expert_offset, expert_offset + num_local_experts) of the
  ones its router chooses among and leaves out what the others would add,
  as the program does.

`params` is the list of weights in creation order: embedding [V, d]; per
layer operator_norm [d], then for a conv layer w_in [d, 3d], filter
[d, L], w_out [d, d], for a full_attention layer wq [d, d], wk and wv
[d, kv * dh], q_norm [dh], k_norm [dh], wo [d, d]; ffn_norm [d]; then for
a dense layer w1 (gate) [d, f], w3 (up) [d, f], w2 [f, d], for an expert
layer router [d, E], expert_bias [E], gate_up [E_held, d, 2 f_e], down
[E_held, f_e, d]; final_norm [d].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x [B, H, T, Dh]: rotate-half over the whole head."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freq[None]
    ang = jnp.concatenate([ang, ang], -1)
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * jnp.cos(ang) + rotated * jnp.sin(ang)


def short_conv(x, w_in, filt, w_out):
    """(C * causal depthwise conv(B * u)) @ w_out, [B, C, u] = split3(x @
    w_in); v_t = sum_j filt[:, j] * (B u)_{t - (L-1) + j}, zeros left of
    t = 0."""
    d, taps = filt.shape
    t = x.shape[1]
    bcx = x @ w_in
    b, c, u = bcx[..., :d], bcx[..., d:2 * d], bcx[..., 2 * d:]
    bu = b * u
    v = jnp.zeros_like(bu)
    for j in range(taps):
        back = taps - 1 - j  # tap j reads the value `back` steps earlier
        shifted = jnp.concatenate(
            [jnp.zeros_like(bu[:, :back]), bu[:, :t - back]], 1)
        v = v + shifted * filt[:, j]
    return (c * v) @ w_out


def attention(cfg, x, wq, wk, wv, q_norm, k_norm, wo):
    b, t, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, eps = d // h, cfg["norm_eps"]

    def heads(y, n):
        return y.reshape(b, t, n, dh)

    # QK-norm on every head by itself, before RoPE
    q = rope(rms_norm(heads(x @ wq, h), q_norm, eps).transpose(0, 2, 1, 3),
             cfg["rope_theta"])
    k = rope(rms_norm(heads(x @ wk, kv), k_norm, eps).transpose(0, 2, 1, 3),
             cfg["rope_theta"])
    v = heads(x @ wv, kv).transpose(0, 2, 1, 3)
    # each key/value head serves h / kv consecutive query heads
    k, v = jnp.repeat(k, h // kv, 1), jnp.repeat(v, h // kv, 1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * dh ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, d) @ wo


def dense_mlp(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def moe(cfg, x, router, expert_bias, gate_up, down):
    """-> (y, chosen experts [N, k]).  gate_up / down hold the experts
    [expert_offset, expert_offset + their leading dimension)."""
    k = cfg["num_experts_per_tok"]
    offset, f = int(cfg.get("expert_offset", 0)), down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    s = jax.nn.sigmoid(x2 @ router)
    chooser = s if expert_bias is None else s + jax.lax.stop_gradient(
        expert_bias)
    _, top_e = jax.lax.top_k(chooser, k)
    top_p = jnp.take_along_axis(s, top_e, -1)
    if cfg.get("norm_topk_prob"):
        top_p = top_p / (top_p.sum(-1, keepdims=True) + 1e-6)
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
    eps = cfg["norm_eps"]
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    emb = next(it)
    x, chosen = emb[ids], []
    for i, kind in enumerate(cfg["layer_types"]):
        h = rms_norm(x, next(it), eps)
        if kind == "conv":
            x = x + short_conv(h, *take(3))
        elif kind == "full_attention":
            x = x + attention(cfg, h, *take(6))
        else:
            raise ValueError("unknown layer type %r" % (kind,))
        h = rms_norm(x, next(it), eps)
        if i < cfg["num_dense_layers"]:
            x = x + dense_mlp(h, *take(3))
        else:
            router = next(it)
            bias = next(it) if cfg.get("use_expert_bias", True) else None
            y, top_e = moe(cfg, h, router, bias, *take(2))
            x = x + y
            chosen.append(top_e)
    logits = rms_norm(x, next(it), eps) @ emb.T
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    return logits, chosen


def loss(cfg, params, batch):
    """Weighted token cross-entropy."""
    logits, _ = forward(cfg, params, jnp.asarray(batch["ids"]))
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"], jnp.float32)
    return ((lse - picked) * w).sum() / w.sum()


def loss_and_grads(cfg, params, batch):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
