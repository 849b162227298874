"""kanana-2-30b-a3b's forward pass and loss in plain float32 jax.numpy:
the reference `models/kanana2.py` (through Executor.run) is tested
against.  No import from the code under test; no kernel, no sort, no
grouped matmul, no cache: attention is an explicit [T, T] softmax under a
tril mask, the experts are a loop over a boolean mask, RoPE is written out
on the published (2i, 2i+1) pairs, gradients are jax.grad.

    x = Emb[ids]
    for layer i:  x += MLA(rms(x)); x += F_i(rms(x))
    logits = rms(x) @ W_head

  MLA   q = h W_q -> [H, nope + rope], or under a `q_lora_rank` q =
        rms(h W_qa; own gain) W_qb; [c, k_rot] = h W_kva -> [r], [rope];
        [k_nope, v] = rms(c; own gain) W_kvb -> [H, nope], [H, dv];
        RoPE on q's rotary part and on k_rot (ONE for all heads), pairs
        (2i, 2i+1), angle t theta^(-2i / rope);
        o = softmax([q_nope, q_rot] [k_nope, k_rot]^T (nope + rope)^-0.5,
        causal) v; MLA = concat(o) W_o.
  F_i   i < first_k_dense_replace: (silu(h W1) * h W3) W2; else
        Shared(h) + Routed(h): Shared the same MLP at n_shared x f_e;
        Routed: s = sigmoid(h W_r); chosen = top-k of s + b; w = s[chosen]
        / (sum + 1e-20) * routed_scaling_factor; sum over the chosen
        experts THIS share holds of w_e SwiGLU_e(h).

Departures from the published model, each on purpose:
- `e_score_correction_bias` is an input like any weight, without
  gradient, as in the published code (what a training program does to it
  between steps, `expert_bias_update`, is no part of a loss);
- the rotated parts are left in the published interleaved order (the
  published code de-interleaves both q and k first: the same permutation
  on both, which no score sees);
- a packed sequence carries no document mask;
- gate and up projections of an expert are one [d, 2f] matrix (gate in
  the first f columns): the same numbers, stored side by side;
- a chip's share: given `num_local_experts` < `n_routed_experts` the
  mixture holds experts [expert_offset, expert_offset + num_local_experts)
  of the ones its router chooses among and leaves out what the others
  would add, as the program does; the shared expert is whole on every
  share.

`params` is the list of weights in creation order: embedding [V, d]; per
layer attn_norm [d], W_q [d, H (nope + rope)] (under a `q_lora_rank` the
three W_qa [d, r_q], q_a_norm [r_q], W_qb [r_q, H (nope + rope)] in its
place), W_kva [d, r + rope], kv_a_norm [r], W_kvb [r, H (nope + dv)], W_o [H dv, d], ffn_norm [d]; then
for a dense layer w1 (gate) [d, f], w3 (up) [d, f], w2 [f, d], for an
expert layer router [d, E], bias [E], gate_up [E_held, d, 2 f_e], down
[E_held, f_e, d], shared w1 [d, n_s f_e], w3, w2 [n_s f_e, d]; final_norm
[d]; head [d, V].
"""

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope_pairs(x, theta):
    """x [..., T, D]: the pair (x[2i], x[2i+1]) turned by t theta^(-2i/D),
    left where it was."""
    d = x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[-2], dtype=jnp.float32)[:, None] * freq[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(
        x.shape)


def latent_attention(cfg, x, wq, wkva, kv_norm, wkvb, wo):
    """`wq` is the list of the query's parameters: [W_q], or [W_qa,
    q_a_norm, W_qb] under a query latent."""
    b, t, _ = x.shape
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rot, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"])
    theta = float(cfg["rope_theta"])
    if len(wq) == 3:
        q = rms_norm(x @ wq[0], wq[1], cfg["rms_norm_eps"]) @ wq[2]
    else:
        q = x @ wq[0]
    q = q.reshape(b, t, h, nope + rot).transpose(0, 2, 1, 3)
    latent = x @ wkva
    c, k_rot = latent[..., :r], latent[..., r:]
    kv = (rms_norm(c, kv_norm, cfg["rms_norm_eps"]) @ wkvb).reshape(
        b, t, h, nope + dv).transpose(0, 2, 1, 3)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rope_pairs(q[..., nope:], theta)], -1)
    k_rot = jnp.broadcast_to(rope_pairs(k_rot, theta)[:, None],
                             (b, h, t, rot))
    k = jnp.concatenate([k_nope, k_rot], -1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (nope + rot) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
    return ctx.transpose(0, 2, 1, 3).reshape(b, t, h * dv) @ wo


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
    if cfg.get("norm_topk_prob", True):
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


def block(cfg, x, i, take):
    """Layer i of the family's stack -> (x, the chosen experts [N, k] of an
    expert layer or None); `take(n)` hands out the next n parameters."""
    eps = cfg["rms_norm_eps"]
    h = rms_norm(x, *take(1), eps)
    x = x + latent_attention(
        cfg, h, take(3 if cfg.get("q_lora_rank") else 1), *take(4))
    h = rms_norm(x, *take(1), eps)
    if i < cfg["first_k_dense_replace"]:
        return x + swiglu_mlp(h, *take(3)), None
    y, top_e = routed(cfg, h, *take(4))
    if cfg["n_shared_experts"]:
        y = y + swiglu_mlp(h, *take(3))
    return x + y, top_e


def taker(params):
    """-> (take(n): the next n parameters, done(): raises unless every one
    was taken)."""
    it = iter(params)

    def take(n):
        return [next(it) for _ in range(n)]

    def done():
        if next(it, None) is not None:
            raise ValueError("reference did not consume every parameter")

    return take, done


def forward(cfg, params, ids):
    """-> ([B, T, V] logits, [per expert layer chosen experts])."""
    take, done = taker(params)
    x, chosen = take(1)[0][ids], []
    for i in range(cfg["num_hidden_layers"]):
        x, top_e = block(cfg, x, i, take)
        if top_e is not None:
            chosen.append(top_e)
    logits = rms_norm(x, *take(1), cfg["rms_norm_eps"]) @ take(1)[0]
    done()
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
