"""JoyAI-LLM-Flash's forward pass, both losses and, through jax.grad, every
gradient in plain float32 jax.numpy: the reference `models/joyai_flash.py`
(through Executor.run) is tested against.  No import from the code under
test; no kernel, no fused op.  The block (latent attention with its query
latent as an explicit [T, T] softmax under a tril mask, the experts as a
loop over a boolean mask, a chip's share passed in as `n_routed_experts` /
`expert_offset` and the held experts' leading dimension) is
`kanana2_reference.block`: both models are the published `deepseek_v3`
modeling code.

    x = Emb[ids];  for layer i:  x = Block_i(x)
    logits  = rms(x; g_f) W_head                    L_main over labels
    h'      = [rms(x; g_h) ; rms(Emb[labels]; g_e)] W_eh
    logits' = rms(Block_mtp(h'); g_s) W_head        L_mtp over labels
              moved one to the left, weights moved with them, the last 0
    L = L_main + mtp_loss_weight L_mtp

x is the last trunk block's output BEFORE g_f (the report's h^0); Emb and
W_head are the trunk's own matrices; Block_mtp is a block of the expert
kind, causal over the same T positions.

`params` is the list of weights in creation order: embedding [V, d]; the
trunk's layers as `kanana2_reference` lists them (with the query latent's
three); final_norm [d]; then, where `num_nextn_predict_layers` is 1,
mtp_hnorm [d], mtp_enorm [d], W_eh [2d, d], the module's block as an expert
layer, mtp_final_norm [d]; head [d, V].
"""

import jax
import jax.numpy as jnp

from .kanana2_reference import block, rms_norm, taker


def forward(cfg, params, ids, labels):
    """-> ([B, T, V] logits, [B, T, V] the module's logits or None)."""
    eps = cfg["rms_norm_eps"]
    take, done = taker(params)
    emb = take(1)[0]
    x = emb[ids]
    for i in range(cfg["num_hidden_layers"]):
        x, _ = block(cfg, x, i, take)
    rows, more = rms_norm(x, *take(1), eps), None
    if cfg.get("num_nextn_predict_layers"):
        u = rms_norm(x, *take(1), eps)
        e = rms_norm(emb[labels], *take(1), eps)
        h = jnp.concatenate([u, e], -1) @ take(1)[0]
        h, _ = block(cfg, h, cfg["first_k_dense_replace"], take)
        more = rms_norm(h, *take(1), eps)
    head = take(1)[0]
    done()
    return rows @ head, None if more is None else more @ head


def _costs(logits, labels):
    lse = jax.scipy.special.logsumexp(logits, -1)
    return lse - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]


def moved_left(x, filler):
    """[x_1 .. x_{T-1}, filler] along the last axis."""
    return jnp.concatenate([x[..., 1:], filler], -1)


def losses(cfg, params, batch):
    """-> (L, L_main, L_mtp or None)."""
    ids, labels = jnp.asarray(batch["ids"]), jnp.asarray(batch["labels"])
    w = jnp.asarray(batch["loss_weight"], jnp.float32)
    logits, more = forward(cfg, params, ids, labels)
    main = (_costs(logits, labels) * w).sum() / w.sum()
    if more is None:
        return main, main, None
    # the last position's target is not in the feed: weight 0
    targets = moved_left(labels, labels[..., -1:])
    w = moved_left(w, jnp.zeros_like(w[..., -1:]))
    mtp = (_costs(more, targets) * w).sum() / w.sum()
    return main + cfg["mtp_loss_weight"] * mtp, main, mtp


def loss(cfg, params, batch):
    return losses(cfg, params, batch)[0]


def loss_and_grads(cfg, params, batch):
    params = [jnp.asarray(p, jnp.float32) for p in params]
    with jax.default_matmul_precision("highest"):
        return jax.jit(jax.value_and_grad(
            lambda p: loss(cfg, p, batch)))(params)
