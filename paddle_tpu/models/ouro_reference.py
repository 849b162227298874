"""Ouro's forward pass and loss in plain float32 jax.numpy: the reference
`models/ouro.py` (through Executor.run) is tested against.  No import from
the code under test; no kernel: the loop is a Python loop over the same
weights, attention is a full [T, T] softmax under a tril mask, RoPE is
rotate-half over the whole head, the exit distribution is written as the
paper writes it (products of probabilities), gradients are jax.grad.

Taken from the published code and paper, each on purpose:
- the final norm is applied inside the loop, after every step's layers
  (the published modeling_ouro.py), so the next step, the head and the
  gate all read the normed state;
- the last step takes the rest of the exit distribution: its own gate is
  not used;
- the loss is the paper's first-stage objective under a uniform prior:
  the expected cross-entropy over the exit steps less beta times the exit
  distribution's entropy; nothing is detached;
- a packed sequence carries no document mask.

`params` is the list of weights in creation order: embedding; per layer
attn_norm, wq, wk, wv, wo, attn_post_norm, ffn_norm, w_gate, w_up, w_down,
ffn_post_norm; final norm; head [d, V]; with more than one loop step the
exit gate's weight [d] and bias [1].

`departure` names one deliberate error, for the tests that show the
comparison catches it: "three_steps" (one loop step fewer), "no_entropy"
(beta 0), "gate_before_norm" (the gate reads the state before the final
norm), "gate_at_last_step" (the last step weighs by its own gate, not by
what is left).
"""

import jax
import jax.numpy as jnp

DEPARTURES = ("three_steps", "no_entropy", "gate_before_norm",
              "gate_at_last_step")


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


def attention(cfg, x, wq, wk, wv, wo):
    b, t, d = x.shape
    h = cfg["num_attention_heads"]

    def heads(y):
        return y.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)

    q = rope(heads(x @ wq), float(cfg["rope_theta"]))
    k = rope(heads(x @ wk), float(cfg["rope_theta"]))
    v = heads(x @ wv)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (d // h) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, d) @ wo


def layer(cfg, x, w):
    (attn_norm, wq, wk, wv, wo, attn_post_norm, ffn_norm, w_gate, w_up,
     w_down, ffn_post_norm) = w
    eps = cfg["rms_norm_eps"]
    a = attention(cfg, rms_norm(x, attn_norm, eps), wq, wk, wv, wo)
    x = x + rms_norm(a, attn_post_norm, eps)
    h = rms_norm(x, ffn_norm, eps)
    m = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
    return x + rms_norm(m, ffn_post_norm, eps)


def exit_distribution(gates):
    """[T_ut - 1, ...] gate probabilities -> q [T_ut, ...]: q_t = lambda_t
    S_t, S_t = prod_{j<t} (1 - lambda_j), and the last step takes S_T."""
    q, left = [], jnp.ones_like(gates[0])
    for lam in gates:
        q.append(lam * left)
        left = left * (1.0 - lam)
    return jnp.stack(q + [left])


def token_cost(cfg, params, ids, labels, departure=None):
    """-> (cost [B, T] of every token, q [T_ut, B, T])."""
    if departure not in (None,) + DEPARTURES:
        raise ValueError("unknown departure %r" % (departure,))
    eps, n_layers = cfg["rms_norm_eps"], cfg["num_hidden_layers"]
    n_ut = cfg["total_ut_steps"] - (departure == "three_steps")
    beta = 0.0 if departure == "no_entropy" else cfg["exit_entropy_beta"]
    it = iter(params)
    emb = next(it)
    stack = [[next(it) for _ in range(11)] for _ in range(n_layers)]
    final_norm, head = next(it), next(it)
    w_g, b_g = (next(it), next(it)) if cfg["total_ut_steps"] > 1 else (0, 0)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")

    x = emb[ids]
    costs, gates = [], []
    for _ in range(n_ut):
        for w in stack:
            x = layer(cfg, x, w)
        raw, x = x, rms_norm(x, final_norm, eps)
        logits = x @ head
        costs.append(jax.scipy.special.logsumexp(logits, -1)
                     - jnp.take_along_axis(logits, labels[..., None],
                                           -1)[..., 0])
        read = raw if departure == "gate_before_norm" else x
        gates.append(jax.nn.sigmoid(jnp.sum(read * w_g, -1) + b_g))
    costs = jnp.stack(costs)
    if n_ut == 1:
        return costs[0], jnp.ones_like(costs)
    q = exit_distribution(gates[:-1])
    if departure == "gate_at_last_step":
        q = q.at[-1].multiply(gates[-1])
    entropy = -jax.scipy.special.xlogy(q, q).sum(0)
    return (q * costs).sum(0) - beta * entropy, q


def loss(cfg, params, batch, departure=None):
    """Weighted mean over the tokens of the expected loss over the exit
    steps less beta times the exit distribution's entropy."""
    cost, _ = token_cost(cfg, params, jnp.asarray(batch["ids"]),
                         jnp.asarray(batch["labels"]), departure)
    w = jnp.asarray(batch["loss_weight"], jnp.float32)
    return (cost * w).sum() / w.sum()


def mean_exit_step(cfg, params, batch):
    """sum_t t mean_n q_t: 1 .. total_ut_steps."""
    _, q = token_cost(cfg, params, jnp.asarray(batch["ids"]),
                      jnp.asarray(batch["labels"]))
    return float((jnp.arange(1, q.shape[0] + 1)
                  * q.mean(tuple(range(1, q.ndim)))).sum())


def loss_and_grads(cfg, params, batch, departure=None):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: loss(cfg, p, batch, departure))(params)
