"""CTC ops (operators/warpctc_op.cc, ctc_align_op.cc, edit_distance_op.cc).

The reference dlopens warp-ctc (platform/dynload/warpctc.h); on TPU the CTC
loss is a log-domain alpha recursion compiled by XLA (via optax.ctc_loss),
batched over the whole padded batch — no external library.
"""

import jax
import jax.numpy as jnp

from ..core.registry import register


@register("warpctc", no_grad_inputs=("Label", "LogitsLength", "LabelLength"))
def _warpctc(ctx, ins, attrs):
    """CTC loss. Padded layout: Logits [B, T, C] (unnormalized), Label
    [B, L] int32 (0..C-2; blank index per attr), LogitsLength [B],
    LabelLength [B]. Output Loss [B, 1]."""
    # imported where it is used: optax is half of `import paddle_tpu`'s time
    # (0.34 of 0.69 s), which every process pays and only this op needs
    import optax

    logits = ins["Logits"][0]
    label = ins["Label"][0]
    b, t, c = logits.shape
    blank = attrs.get("blank", 0)
    if ins.get("LogitsLength"):
        llen = ins["LogitsLength"][0].reshape(-1).astype(jnp.int32)
    else:
        llen = jnp.full((b,), t, jnp.int32)
    if ins.get("LabelLength"):
        lablen = ins["LabelLength"][0].reshape(-1).astype(jnp.int32)
    else:
        lablen = jnp.full((b,), label.shape[1], jnp.int32)
    tpos = jnp.arange(t)[None, :]
    logit_pad = (tpos >= llen[:, None]).astype(jnp.float32)
    lpos = jnp.arange(label.shape[1])[None, :]
    label_pad = (lpos >= lablen[:, None]).astype(jnp.float32)
    # optax expects blank==0; rotate classes if needed. Labels arrive
    # compressed over the C-1 non-blank classes (0..C-2): compressed l is
    # full class l (l < blank) or l+1 (l >= blank), and after rotating the
    # logits so blank sits at 0, both cases land on index l+1.
    if blank != 0:
        perm = jnp.concatenate(
            [jnp.asarray([blank]), jnp.delete(jnp.arange(c), blank, assume_unique_indices=True)]
        )
        logits = logits[:, :, perm]
    label = label + 1
    loss = optax.ctc_loss(logits, logit_pad, label.astype(jnp.int32), label_pad)
    norm = attrs.get("norm_by_times", False)
    if norm:
        loss = loss / jnp.maximum(llen.astype(loss.dtype), 1.0)
    return {"Loss": [loss.reshape(-1, 1)], "WarpCTCGrad": [jnp.zeros_like(logits)]}


@register("ctc_align", no_grad_inputs=("Input", "InputLength"))
def _ctc_align(ctx, ins, attrs):
    """Remove repeats then blanks (ctc_align_op.cc). Padded [B, T] int;
    output padded [B, T] with -1 (or pad_value) past the decoded length,
    plus OutputLength [B]."""
    x = ins["Input"][0].astype(jnp.int32)
    blank = attrs.get("blank", 0)
    pad_value = attrs.get("padding_value", 0)
    b, t = x.shape
    if ins.get("InputLength"):
        ilen = ins["InputLength"][0].reshape(-1).astype(jnp.int32)
    else:
        ilen = jnp.full((b,), t, jnp.int32)
    pos = jnp.arange(t)[None, :]
    valid = pos < ilen[:, None]
    prev = jnp.concatenate([jnp.full((b, 1), -1, x.dtype), x[:, :-1]], axis=1)
    keep = valid & (x != blank) & (x != prev)
    # stable compaction: dest index = cumsum(keep) - 1
    dest = jnp.cumsum(keep.astype(jnp.int32), axis=1) - 1
    out_len = jnp.maximum(dest[:, -1] + 1, 0)
    out = jnp.full((b, t), pad_value, x.dtype)
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, t))
    dest_safe = jnp.where(keep, dest, t - 1)
    # scatter kept values; use add-safe set with masked dummy column trick
    out = out.at[rows, dest_safe].set(jnp.where(keep, x, out[rows, dest_safe]))
    return {"Output": [out], "OutputLength": [out_len.reshape(-1, 1)]}


@register("edit_distance", no_grad_inputs=("Hyps", "Refs", "HypsLength", "RefsLength"))
def _edit_distance(ctx, ins, attrs):
    """Levenshtein distance per pair (edit_distance_op.cc). Padded
    Hyps [B, M], Refs [B, N] + lengths; DP over the reference axis via
    lax.scan, vectorized over batch and hyp axis."""
    hyp = ins["Hyps"][0].astype(jnp.int32)
    ref = ins["Refs"][0].astype(jnp.int32)
    b, m = hyp.shape
    n = ref.shape[1]
    if ins.get("HypsLength"):
        hlen = ins["HypsLength"][0].reshape(-1).astype(jnp.int32)
    else:
        hlen = jnp.full((b,), m, jnp.int32)
    if ins.get("RefsLength"):
        rlen = ins["RefsLength"][0].reshape(-1).astype(jnp.int32)
    else:
        rlen = jnp.full((b,), n, jnp.int32)

    # row DP: dist[j] over hyp prefix length j (0..m)
    row0 = jnp.broadcast_to(jnp.arange(m + 1, dtype=jnp.float32), (b, m + 1))

    def step(row, i):
        # process ref token i (0-based); new row over hyp prefixes
        r_i = jnp.take_along_axis(ref, jnp.minimum(i, n - 1)[None, None].repeat(b, 0), axis=1)[:, 0]
        sub_cost = (hyp != r_i[:, None]).astype(jnp.float32)  # [B, M]
        # new[0] = i+1
        def inner(carry, j):
            # carry = new[j]; compute new[j+1]
            prev_new = carry
            dele = row[:, j + 1] + 1.0
            ins_ = prev_new + 1.0
            sub = row[:, j] + sub_cost[:, j]
            val = jnp.minimum(jnp.minimum(dele, ins_), sub)
            return val, val

        first = jnp.full((b,), (i + 1).astype(jnp.float32))
        _, rest = jax.lax.scan(inner, first, jnp.arange(m))
        new_row = jnp.concatenate([first[:, None], jnp.swapaxes(rest, 0, 1)], axis=1)
        active = (i < rlen)[:, None]
        return jnp.where(active, new_row, row), None

    row, _ = jax.lax.scan(step, row0, jnp.arange(n))
    dist = jnp.take_along_axis(row, hlen[:, None], axis=1)[:, 0]
    # empty-ref convention: distance = hyp length
    dist = jnp.where(rlen == 0, hlen.astype(dist.dtype), dist)
    if attrs.get("normalized", True):
        dist = dist / jnp.maximum(rlen.astype(dist.dtype), 1.0)
    return {
        "Out": [dist.reshape(-1, 1)],
        "SequenceNum": [jnp.asarray(b, jnp.int32)],
    }
