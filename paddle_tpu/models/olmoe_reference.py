"""OLMoE's forward pass and loss in plain float32 jax.numpy: the reference
`models/olmoe.py` (through Executor.run) is tested against.  No import
from the code under test; no kernel, no sort, no grouped matmul: the
experts are a loop over a boolean mask, attention is a full [T, T] softmax
under a tril mask, RoPE is rotate-half over the whole head, gradients are
jax.grad.

Departures from the published model, each on purpose:
- the load-balance and z-loss weights (0.01, 0.001) are the paper's
  (arXiv:2409.02060 section 4.1), not config.json's, which has no z-loss;
- a packed sequence carries no document mask: every position attends to
  every earlier one;
- gate and up projections of an expert are one [d, 2f] matrix (gate in
  the first f columns): the same numbers, stored side by side.

`params` is the list of weights in creation order: embedding; per layer
attn_norm, wq, wk, wv, q_norm, k_norm, wo, ffn_norm, router [d, E],
gate_up [E, d, 2f], down [E, f, d]; final_norm; head [d, V].
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


def attention(cfg, x, wq, wk, wv, q_norm, k_norm, wo):
    b, t, d = x.shape
    h, eps = cfg["num_attention_heads"], cfg["rms_norm_eps"]

    def heads(y):
        return y.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)

    # QK-norm over all heads jointly, before the split
    q = rope(heads(rms_norm(x @ wq, q_norm, eps)), cfg["rope_theta"])
    k = rope(heads(rms_norm(x @ wk, k_norm, eps)), cfg["rope_theta"])
    v = heads(x @ wv)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d // h) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, d) @ wo


def moe(cfg, x, router, gate_up, down):
    """-> (y, load-balance loss, z loss, chosen experts [N, k])."""
    n_experts, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    f = down.shape[1]
    x2 = x.reshape(-1, x.shape[-1])
    logits = x2 @ router
    probs = jax.nn.softmax(logits, -1)
    top_p, top_e = jax.lax.top_k(probs, k)
    if cfg.get("norm_topk_prob"):
        top_p = top_p / top_p.sum(-1, keepdims=True)
    y = jnp.zeros_like(x2)
    for e in range(n_experts):
        chosen = top_e == e  # [N, k]
        weight = jnp.where(chosen, top_p, 0.0).sum(-1, keepdims=True)
        gu = x2 @ gate_up[e]
        out = (jax.nn.silu(gu[:, :f]) * gu[:, f:]) @ down[e]
        y = y + jnp.where(chosen.any(-1, keepdims=True), weight * out, 0.0)
    frac = jax.lax.stop_gradient(
        (top_e[..., None] == jnp.arange(n_experts)).sum((0, 1))
        / x2.shape[0])
    lb = n_experts * jnp.sum(frac * probs.mean(0))
    z = jnp.mean(jax.scipy.special.logsumexp(logits, -1) ** 2)
    return y.reshape(x.shape), lb, z, top_e


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, router loss, [per layer chosen experts])."""
    eps = cfg["rms_norm_eps"]
    it = iter(params)
    x = next(it)[ids]
    router_loss, chosen = 0.0, []
    for _ in range(cfg["num_hidden_layers"]):
        attn_norm, wq, wk, wv, q_norm, k_norm, wo = (next(it)
                                                     for _ in range(7))
        x = x + attention(cfg, rms_norm(x, attn_norm, eps), wq, wk, wv,
                          q_norm, k_norm, wo)
        ffn_norm, router, gate_up, down = (next(it) for _ in range(4))
        y, lb, z, top_e = moe(cfg, rms_norm(x, ffn_norm, eps), router,
                              gate_up, down)
        x = x + y
        router_loss = (router_loss + cfg["router_aux_loss_coef"] * lb
                       + cfg["router_z_loss_coef"] * z)
        chosen.append(top_e)
    x = rms_norm(x, next(it), eps)
    logits = x @ next(it)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    return logits, router_loss, chosen


def loss(cfg, params, batch):
    """Weighted token cross-entropy + the router losses."""
    logits, router_loss, _ = forward(cfg, params, jnp.asarray(batch["ids"]))
    lse = jax.scipy.special.logsumexp(logits, -1)
    picked = jnp.take_along_axis(
        logits, jnp.asarray(batch["labels"])[..., None], -1)[..., 0]
    w = jnp.asarray(batch["loss_weight"], jnp.float32)
    return ((lse - picked) * w).sum() / w.sum() + router_loss


def loss_and_grads(cfg, params, batch):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: loss(cfg, p, batch))(params)
