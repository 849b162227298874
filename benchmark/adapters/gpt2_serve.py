"""Adapter: GPT-2 served through paddle_tpu.serving.ServingEngine (one
compiled ragged wide-step program over a pool of cache slots).  A workload
of kind "serve" names it under "adapter"; the configuration's file
(`configs/gpt2_345m.json`, its "model" group) is the training cells' own.

What loops/serve.py asks of a serve adapter:

  build_serve(cfg, work)            the program's side: hp for ServingEngine
  make_weights(cfg, seed)           every parameter, made on the device in
                                    one jitted call from the seed, float32,
                                    in the order the architecture creates
                                    them: the PROGRAM is given them (as a
                                    checkpoint would be loaded) and the
                                    reference draws them again, after the
                                    window, from the same seed
  serve_flops / serve_step_bytes    closed forms over shapes
  reference_logits / control_logits the plain reference and its control
  SERVE_TOLERANCE                   with the readings they were set from

The reference imports nothing of the program and is handed nothing the
program made.
"""

import json

import numpy as np

# The numbers loops/serve.py compare()s, over the greedy requests it
# watched, at every output position: how far the row of logits the timed
# step fetched lies from the reference's row (the reference: float32
# weights, one full causal pass over prompt + served tokens, at each of
# REFERENCES below; the nearer counts), as the norm of the difference over
# the norm of the reference's row about its mean; and the served tokens that are not the first choice
# of their own row (a greedy token is, exactly).  The limits and the
# readings they were set from: PERF.md section 2.
SERVE_TOLERANCE = {"logit_err_mean": 0.008, "logit_err_max": 0.010,
                   "off_argmax": 0}


def _model(cfg):
    return cfg["model"]


def _hp(model):
    from paddle_tpu.models import gpt2

    class HP(gpt2.GPT2Config):
        pass

    for k, v in model.items():
        setattr(HP, k, v)
    HP.dropout = 0.0
    return HP


def build_serve(cfg, work):
    """What ServingEngine needs beside the executor: the model's sizes."""
    return {"hp": _hp(_model(cfg))}


# --------------------------------------------------------------------------
# weights: the creation order of models/gpt2.py's builders, which is the
# order _hidden() below consumes them in
# --------------------------------------------------------------------------
def param_shapes(m):
    """[(how to draw, shape)] of every parameter, in creation order."""
    d, v = m["d_model"], m["vocab_size"]
    out = [("w", (v, d)), ("w", (m["n_ctx"], d))]
    for _ in range(m["n_layer"]):
        out += [("g", (d,)), ("b", (d,)),
                ("w", (d, d)), ("w", (d, d)), ("w", (d, d)), ("w", (d, d)),
                ("g", (d,)), ("b", (d,)),
                ("w", (d, 4 * d)), ("b", (4 * d,)),
                ("w", (4 * d, d)), ("b", (d,))]
    out += [("g", (d,)), ("b", (d,))]
    if not m.get("tie_embeddings"):
        out.append(("w", (d, v)))
    return out


def make_weights(cfg, seed):
    """Float32 parameters from the seed, on the default device, one jitted
    call: matrices N(0, 0.02) as GPT-2 initialises them, layer-norm gains
    1 + N(0, 0.05), biases N(0, 0.02) (a missing bias or gain must show)."""
    import jax
    import jax.numpy as jnp

    shapes = param_shapes(_model(cfg))

    def draw(key):
        keys = jax.random.split(key, len(shapes))
        out = []
        for k, (how, shape) in zip(keys, shapes):
            x = jax.random.normal(k, shape, jnp.float32)
            out.append(1.0 + 0.05 * x if how == "g" else 0.02 * x)
        return out

    return jax.jit(draw)(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))


# --------------------------------------------------------------------------
# closed forms
# --------------------------------------------------------------------------
def matmul_params(m):
    """Parameters every token is multiplied with in the layers (q, k, v, o
    and the MLP); the head is counted per sampled row."""
    return m["n_layer"] * 12 * m["d_model"] ** 2


def serve_flops(cfg, columns, context_sum, sampled_rows):
    """Matmul operations, forward only, that serving REQUIRES: 2 x matmul
    parameters for each real step column (a prompt or an output token),
    QK^T and PV over the keys each column may see (`context_sum`: the sum
    over columns of position + 1), and the vocabulary head for each row a
    token was sampled from.  Padding columns, free slots and the head rows
    the step computes and nobody samples are not counted."""
    m = _model(cfg)
    d = m["d_model"]
    return (2.0 * matmul_params(m) * columns
            + 4.0 * d * m["n_layer"] * context_sum
            + 2.0 * d * m["vocab_size"] * sampled_rows)


def cache_row_bytes(cfg, work):
    """Bytes one cached position holds: K and V of every layer."""
    m = _model(cfg)
    item = np.dtype(work["engine"]["cache_dtype"]).itemsize
    return m["n_layer"] * 2 * m["d_model"] * item


def serve_step_bytes(cfg, work, steps, rows_read, rows_written):
    """Bytes `steps` engine steps MUST move through HBM: the float32 layer
    weights and the tied head's table once a step, the live cache rows read
    and the new rows written.  Activations, the logits and whatever the
    program moves beyond that (the rest of a slot's t_max rows, padding
    columns) are not counted: the share of the roofline can only read low."""
    m = _model(cfg)
    weights = 4.0 * (matmul_params(m) + m["vocab_size"] * m["d_model"])
    return steps * weights + cache_row_bytes(cfg, work) * (
        rows_read + rows_written)


# --------------------------------------------------------------------------
# plain reference: pre-LN decoder, gelu(erf) MLP, learned positions, one
# sequence, one full causal pass
# --------------------------------------------------------------------------
def _hidden(m, weights, ids, dtype):
    """Final layer-normed hidden states [T, d] of one sequence, computed in
    `dtype` throughout, and the head's matrix [d, V]."""
    import jax
    import jax.numpy as jnp

    d, h = m["d_model"], m["n_head"]
    it = iter([w.astype(dtype) for w in weights])
    t = ids.shape[0]

    def layer_norm(x):
        g, b = next(it), next(it)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + 1e-5) * g + b

    def attention(x):
        wq, wk, wv, wo = next(it), next(it), next(it), next(it)

        def heads(y):
            return y.reshape(t, h, d // h).transpose(1, 0, 2)

        q, k, val = heads(x @ wq), heads(x @ wk), heads(x @ wv)
        s = jnp.einsum("hqd,hkd->hqk", q, k) * (d // h) ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        ctx = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), val)
        return ctx.transpose(1, 0, 2).reshape(t, d) @ wo

    def mlp(x):
        w1, b1, w2, b2 = next(it), next(it), next(it), next(it)
        return jax.nn.gelu(x @ w1 + b1, approximate=False) @ w2 + b2

    emb, pos = next(it), next(it)
    x = emb[ids] + pos[:t]
    for _ in range(m["n_layer"]):
        x = x + attention(layer_norm(x))
        x = x + mlp(layer_norm(x))
    x = layer_norm(x)
    head = emb.T if m.get("tie_embeddings") else next(it)
    if next(it, None) is not None:
        raise ValueError("reference did not consume every parameter")
    return x, head


def _padded(m, prompt, tokens, t_max, n_rows):
    """One request as fixed shapes (one compile): the sequence prompt +
    served tokens but the last, padded to t_max (causal: padding after a
    position cannot reach it); the rows that predicted each served token
    (padding rows repeat row 0 and are cut off by the caller)."""
    prompt = np.asarray(prompt, "int64")
    tokens = np.asarray(tokens, "int64")
    seq = np.concatenate([prompt, tokens[:-1]])
    if seq.size > t_max or tokens.size > n_rows:
        raise ValueError("request of %d + %d tokens exceeds the reference's "
                         "shapes (%d, %d)" % (prompt.size, tokens.size,
                                              t_max, n_rows))
    ids = np.zeros(t_max, "int32")
    ids[:seq.size] = seq
    rows = np.zeros(n_rows, "int32")
    rows[:tokens.size] = prompt.size - 1 + np.arange(tokens.size)
    return ids, rows


_JITTED = {}


def _logits_fn(m, dtype, precision):
    """jitted (weights, ids [T], rows [R]) -> logits [R, V] float32 of one
    full causal pass computed in `dtype` throughout, matmuls at
    `precision`."""
    import jax
    import jax.numpy as jnp

    key = (json.dumps(m, sort_keys=True), str(dtype), precision)
    if key not in _JITTED:
        def logits(weights, ids, rows):
            with jax.default_matmul_precision(precision):
                x, head = _hidden(m, weights, ids, dtype)
                return (x[rows] @ head).astype(jnp.float32)

        _JITTED[key] = jax.jit(logits)
    return _JITTED[key]


def _logits(cfg, work, weights, prompt, tokens, dtype, precision):
    m = _model(cfg)
    t_max = int(work["engine"]["t_max"])
    n_rows = int(work["traffic"]["output_len"]["hi"])
    ids, rows = _padded(m, prompt, tokens, t_max, n_rows)
    out = _logits_fn(m, dtype, precision)(weights, ids, rows)
    return np.asarray(out)[:len(tokens)]


# What a row may be compared with: the pass at the precision the workload
# file states (float32, matmuls at the platform's default: one bfloat16 pass
# on a TPU, exact on a CPU) and the pass above it (matmuls at "highest").  A
# program that computes as stated lies by the first, one that computes more
# exactly than stated by the second; compare() takes the nearer.  Against
# "highest" alone the stated precision itself reads 0.0067-0.0076 and the
# bfloat16 control only 1.9 x that (PERF.md section 2).
REFERENCES = ("default", "highest")


def reference_logits(cfg, work, weights, prompt, tokens):
    """The rows of logits [n, V] that predicted each of the n served tokens
    of ONE request, float32 throughout, once for each of REFERENCES."""
    import jax.numpy as jnp

    return [_logits(cfg, work, weights, prompt, tokens, jnp.float32, p)
            for p in REFERENCES]


def control_logits(cfg, work, weights, prompt, tokens, dtype="bfloat16"):
    """The control: the same rows from the same pass computed in the next
    precision down (bfloat16 throughout: weights, activations, logits), to
    be put in the program's place.  It need not decode."""
    import jax.numpy as jnp

    return _logits(cfg, work, weights, prompt, tokens, jnp.dtype(dtype),
                   "default")
