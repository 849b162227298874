"""Transformer (base config) — encoder/decoder for WMT-style seq2seq.

Capability mirror of the reference's benchmark transformer
(`python/paddle/fluid/tests/unittests/dist_transformer.py:123`
ModelHyperParams / transformer builder), re-designed for TPU: fixed-length
padded batches with explicit attention masks (no LoD), all attention heads
batched into single MXU matmuls, and the whole train step compiled as one
XLA program.  Tensor-parallel sharding rules for the qkv/ffn weights live in
paddle_tpu.parallel (GSPMD replaces the DistributeTranspiler).
"""

import contextlib

import numpy as np

from .. import framework, layers, unique_name
from ..initializer import Normal
from ..param_attr import ParamAttr


def named(base):
    """Named ParamAttr so parallel.transformer_tp_rules can target these
    weights by regex (the GSPMD analog of the transpiler's param slicing)."""
    return ParamAttr(name=unique_name.generate(base))

__all__ = [
    "ModelHyperParams",
    "transformer",
    "latent_attention",
    "wmt_transformer_program",
    "transformer_logits_program",
    "greedy_translate",
    "greedy_translate_cached",
    "beam_translate_cached",
    "sample_translate_cached",
    "transformer_decode_programs",
    "force_decode_logits_cached",
    "beam_translate",
]


class ModelHyperParams:
    """Transformer-base (dist_transformer.py ModelHyperParams parity)."""

    src_vocab_size = 10000
    trg_vocab_size = 10000
    max_length = 256
    d_model = 512
    d_inner_hid = 2048
    n_head = 8
    n_layer = 6
    dropout = 0.1
    label_smooth_eps = 0.1
    recompute = False  # rematerialize each enc/dec layer in backward
    partition_family = "transformer"


def _pos_encoding_table(max_len, d_model):
    pos = np.arange(max_len)[:, None].astype("float64")
    i = np.arange(d_model)[None, :].astype("float64")
    angle = pos / np.power(10000, 2 * (i // 2) / d_model)
    table = np.zeros((max_len, d_model), dtype="float32")
    table[:, 0::2] = np.sin(angle[:, 0::2])
    table[:, 1::2] = np.cos(angle[:, 1::2])
    return table


def _word_emb_attr(d_model):
    """Named so the family's vocab-sharding rule (`emb.w`) covers the
    table: under the default embedding_N.w_0 name both word tables, a
    sixth of the parameters, replicated on every mp shard."""
    return ParamAttr(name=unique_name.generate("word_emb.w"),
                     initializer=Normal(0.0, d_model ** -0.5))


def prepare_embedding(ids, vocab_size, d_model, max_len, dropout_rate, pos_name, is_test=False):
    """Word + sinusoid position embedding (the reference's
    prepare_encoder/decoder), position table as a frozen parameter."""
    word_emb = layers.embedding(
        ids,
        size=[vocab_size, d_model],
        param_attr=_word_emb_attr(d_model),
    )
    word_emb = layers.scale(word_emb, scale=d_model ** 0.5)
    pos_table = layers.create_parameter(
        shape=[max_len, d_model],
        dtype="float32",
        name=pos_name,
        default_initializer=None,
        attr=ParamAttr(
            name=pos_name,
            trainable=False,
            initializer=_NumpyInit(_pos_encoding_table(max_len, d_model)),
        ),
    )
    seq_len = ids.shape[1]
    pos_slice = layers.slice(pos_table, axes=[0], starts=[0], ends=[seq_len])
    out = layers.elementwise_add(word_emb, pos_slice, axis=1)
    if dropout_rate:
        out = layers.dropout(out, dropout_rate, is_test=is_test)
    return out


class _NumpyInit:
    def __init__(self, value):
        self.value = value

    def __call__(self, var, block):
        from ..initializer import NumpyArrayInitializer

        return NumpyArrayInitializer(self.value)(var, block)


def multi_head_attention(
    queries, keys, values, attn_bias, d_model, n_head, dropout_rate=0.0,
    is_test=False, cache=None, fused=False, kpad_bias=None, causal=False,
    n_kv_head=None, rotary=False, qk_norm=False, qk_norm_eps=1e-5,
    rotary_base=10000.0, param_attr=None, head_dim=None, window=0,
    out_gate=False, scopes=False, rotary_dim=None, norm_unit_offset=False,
    rotary_scaling=None,
):
    """All heads in one qkv projection + batched matmuls (MXU-shaped).
    attn_bias: [B, 1 or H, Tq, Tk] additive mask (−1e9 at masked slots).

    fused=True routes through the fused_attention op (the flash kernel
    where the placed platform and the shape choose it, fused XLA
    otherwise): padding is expressed as the rank-1 kpad_bias [B, Tk] and
    causality as a flag, so no [Tq, Tk] mask is built (and, in the
    kernel, no score matrix hits HBM).  Attention-prob dropout is folded away on
    this path (the probs are never materialized) — standard flash-attention
    practice; residual/ffn dropout still applies.

    n_kv_head < n_head enables grouped-query attention (MQA at 1): k/v
    project to n_kv_head heads shared by n_head/n_kv_head query groups.
    On the cached decode path the KV cache AND the per-step K/V reads
    shrink by that factor (query groups fold onto the length-1 time
    axis, no tiling).  On the training paths the kv heads are broadcast
    back to n_head before attention — there the win is parameters and
    kv-projection FLOPs, not attention reads.

    rotary=True applies rotary position embedding (RoPE) to q and k after
    the head split — full-sequence positions arange(T), or the cache's
    current position on the decode path (cached keys store pre-rotated,
    so relative rotations stay exact across steps); rotary_base is RoPE's
    theta.

    qk_norm=True normalises the q and the k projection with an rms_norm
    each (own [d] weight, over all heads jointly, as OLMoE does) before
    the head split, and so before rotary.  qk_norm="head" normalises every
    head on its own over head_dim (one [head_dim] weight for q's heads,
    one for k's, as LFM2 does), after the head split and before rotary.

    q, k and v are of ONE head width, `head_dim` (d_model / n_head where
    none is given: Trinity-Mini's 32 heads of 128 over a hidden size of
    2048 project to 4096 and back); the flash kernel takes it at 64, 128
    or 256 (Qwen3-Next's).  Rotary turns the whole head, or, with
    `rotary_dim` < head_dim, the head's first `rotary_dim` lanes alone
    (`partial_rotary_factor`: Qwen3-Next turns 64 of 256, rotate-half
    pairing (i, i + rotary_dim / 2) inside them, the frequencies those of a
    head `rotary_dim` wide) and leaves the others as projected: a split,
    `rotary_embed` on the first part and a concatenation; without
    `rotary_dim`, or at head_dim, one `rotary_embed` over the head.  Either
    is built under the name scope `rope` where `scopes` is set.  Scores of
    another
    width than the values, and a decoupled rotary part of which the key
    has one for all heads, are `latent_attention`'s.

    rotary_scaling: a published `rope_parameters` group with `rope_type`
    "yarn" (`layers.rotary_embed`'s `scaling`: scaled inverse frequencies
    over the `rotary_dim` lanes and a factor on cos and sin), on the
    training path alone; the cache paths refuse it.

    norm_unit_offset=True: the per-head QK-norm's gain is 1 + w with w
    initialised to zero (`layers.rms_norm(unit_offset=True)`).

    window > 0 (the fused causal training path alone): sliding-window
    attention, key j visible to query i iff 0 <= i - j < window; the
    attribute reaches the `fused_attention` op, whose kernels skip the
    blocks outside the band.  A cache that keeps the last `window`
    positions only is not built here.

    out_gate=True multiplies the heads' output, [B, T, n_head * head_dim],
    by sigmoid(queries W_g) (its own projection, "mha_gate.w", of that
    width) before the output projection.  out_gate="head": one gate a head
    and token, sigmoid(queries W_g) with W_g [d, n_head], broadcast over
    head_dim; the projection is built under `attn_gate` with the sigmoid
    and the product (Laguna's `gating`).  Under the AMP pass either gate's
    sigmoid and product are float32.

    scopes=True builds the `fused_attention` op under the name scope
    `core`, the rotary turn under `rope` (over the whole head or a part of
    it) and the gate's sigmoid and product under `attn_gate`, inside
    whatever scope the caller builds the layer under (trinity's
    `attn_window` / `attn_full`), so that the lowered step says which
    time is the core's; the default builds under none, as every program
    before it.

    RAGGED cache mode (the continuous-batching serving step): a cache
    dict carrying "pos_rows" [B] + "width_rows" [B] (and "pos_mat"
    [B, W] under rotary) instead of the scalar "pos" writes each batch
    row's K/V at ITS OWN position with ITS OWN valid width
    (slot_cache_write: a decoding slot writes 1 token, a prefilling
    slot a chunk, a free slot nothing) and masks attention with
    per-row offset-causal cutoffs (fused_attention vector qstart) —
    one dispatch serves a pool of requests at heterogeneous
    positions.

    param_attr: `base name -> ParamAttr` for the layer's weights
    ("mha_q.w", ...); a builder that runs a layer several times over one
    set of weights gives each use the same names.  The default numbers
    every call's weights anew."""
    pa = param_attr or named
    dh = int(head_dim or d_model // n_head)
    n_kv = n_kv_head or n_head
    if n_head % n_kv:
        raise ValueError(
            "n_kv_head (%d) must divide n_head (%d)" % (n_kv, n_head))
    window = int(window or 0)
    if window and not (fused and causal and cache is None):
        raise ValueError(
            "window (%d) is the fused causal training path's: pass "
            "fused=True, causal=True and no cache" % window)

    def scoped(name):
        return (framework.name_scope(name) if scopes
                else contextlib.nullcontext())

    q = layers.fc(queries, size=n_head * dh, num_flatten_dims=2,
                  bias_attr=False, param_attr=pa("mha_q.w"))
    k = layers.fc(keys, size=n_kv * dh, num_flatten_dims=2, bias_attr=False,
                  param_attr=pa("mha_k.w"))
    v = layers.fc(values, size=n_kv * dh, num_flatten_dims=2, bias_attr=False,
                  param_attr=pa("mha_v.w"))
    if out_gate not in (False, True, "head"):
        raise ValueError("out_gate is False, True (a gate a lane) or 'head', "
                         "got %r" % (out_gate,))
    gate = None
    if out_gate == "head":
        with scoped("attn_gate"):
            gate = layers.fc(queries, size=n_head, num_flatten_dims=2,
                             bias_attr=False, param_attr=pa("mha_gate.w"))
    elif out_gate:
        gate = layers.fc(queries, size=n_head * dh, num_flatten_dims=2,
                         bias_attr=False, param_attr=pa("mha_gate.w"))
    if qk_norm not in (False, True, "head"):
        raise ValueError("qk_norm is False, True (the whole projection) "
                         "or 'head', got %r" % (qk_norm,))
    if qk_norm and qk_norm != "head":
        q = layers.rms_norm(q, epsilon=qk_norm_eps,
                            param_attr=pa("mha_q_norm.w"))
        k = layers.rms_norm(k, epsilon=qk_norm_eps,
                            param_attr=pa("mha_k_norm.w"))

    def split_heads(x, heads, norm_attr=None):
        b, t = x.shape[0], x.shape[1]
        x = layers.reshape(x, [b, t, heads, dh])
        if norm_attr is not None:  # per head: one [dh] weight for all
            x = layers.rms_norm(x, epsilon=qk_norm_eps, param_attr=norm_attr,
                                unit_offset=norm_unit_offset)
        return layers.transpose(x, [0, 2, 1, 3])  # [B, heads, T, Dh]

    def repeat_kv(x):
        """[B, n_kv, T, Dh] -> [B, n_head, T, Dh]: each kv head serves a
        contiguous group of query heads."""
        if n_kv == n_head:
            return x
        g = n_head // n_kv
        b, _, t, _ = x.shape
        x = layers.reshape(x, [b, n_kv, 1, t, dh])
        x = layers.expand(x, [1, 1, g, 1, 1])
        return layers.reshape(x, [b, n_head, t, dh])

    per_head = qk_norm == "head"
    if norm_unit_offset and not per_head:
        raise ValueError("norm_unit_offset is the per-head QK-norm's "
                         "(qk_norm='head')")
    rotary_dim = int(rotary_dim or dh)
    if not 0 < rotary_dim <= dh or rotary_dim % 2:
        raise ValueError("rotary_dim %d is not an even part of head_dim %d"
                         % (rotary_dim, dh))
    if rotary_dim < dh and cache is not None:
        raise ValueError("rotary on a part of the head is the training "
                         "path's: the cache paths rotate the whole head")
    if rotary_scaling is not None and cache is not None:
        raise ValueError("rotary_scaling is the training path's: the cached "
                         "decode paths have no scaled frequencies yet")

    def turn(x, rpos):
        def embed(y):
            return layers.rotary_embed(y, pos=rpos, base=rotary_base,
                                       scaling=rotary_scaling)

        with scoped("rope"):
            if rotary_dim == dh:
                return embed(x)
            turned, kept = layers.split(x, [rotary_dim, dh - rotary_dim],
                                        dim=-1)
            return layers.concat([embed(turned), kept], axis=-1)

    q = split_heads(q, n_head, pa("mha_q_norm.w") if per_head else None)
    k = split_heads(k, n_kv, pa("mha_k_norm.w") if per_head else None)
    v = split_heads(v, n_kv)
    if rotary:
        # ragged serving feeds pos_mat [B, W] (per-row positions);
        # chunked decode feeds pos_vec (positions pos..pos+W-1); the
        # one-token step feeds the scalar pos
        rpos = None
        if cache is not None:
            if "pos_rows" in cache and "pos_mat" not in cache:
                raise ValueError(
                    "ragged cached attention with rotary needs pos_mat "
                    "(per-row absolute positions [B, W]) — without it "
                    "every slot would silently rotate at arange(W)")
            for key in ("pos_mat", "pos_vec", "pos"):
                if key in cache:
                    rpos = cache[key]
                    break
            if rpos is None:
                raise KeyError(
                    "cached rotary attention needs pos/pos_vec/pos_mat")
        q, k = turn(q, rpos), turn(k, rpos)
    if cache is not None:
        if attn_bias is not None or kpad_bias is not None:
            raise ValueError(
                "cached attention owns its <=pos mask; attn_bias/kpad_bias "
                "are not supported on the cache path")
        if causal:
            raise ValueError(
                "cached attention handles causality via the cache mask — "
                "pass causal=False with cache")
        if dropout_rate:
            raise ValueError("cached decode is inference-only: "
                             "dropout_rate must be 0")
        # incremental KV-cached decode: q/k/v are the ONE current token's
        # projections; k/v land in the [B, H, T_max, Dh] cache vars at
        # cache["pos"], and q attends over the cache with a <=pos mask.
        # The cache vars are persistable scope state — the executor's
        # functionalization threads the update back (donated in HBM).
        from ..layer_helper import LayerHelper

        helper = LayerHelper("cached_attention")
        ragged = "pos_rows" in cache
        if ragged and "width_rows" not in cache:
            raise ValueError(
                "ragged cached attention needs width_rows alongside "
                "pos_rows (per-row valid write widths)")

        def write_cache(cvar, new):
            """Updated full-length cache tensor; also assigns it back into
            the persistable var (state threads through the executor)."""
            if ragged:
                out = layers.slot_cache_write(
                    cvar, new, cache["pos_rows"], cache["width_rows"])
            else:
                out = helper.create_variable_for_type_inference(cvar.dtype)
                helper.append_op(
                    "seq_cache_write",
                    inputs={"Cache": [cvar], "New": [new],
                            "Pos": [cache["pos"]]},
                    outputs={"Out": [out]},
                )
            helper.append_op("assign", inputs={"X": [out]},
                             outputs={"Out": [cvar]})
            return out

        if int(cache["k"].shape[1]) != n_kv:
            raise ValueError(
                "cache has %d kv heads but n_kv_head is %d — create the "
                "caches with the model's kv head count"
                % (int(cache["k"].shape[1]), n_kv))
        k_full = write_cache(cache["k"], k)
        v_full = write_cache(cache["v"], v)
        t_max = int(cache["k"].shape[2])
        bsz = int(cache["k"].shape[0])
        width = int(q.shape[2])
        def pos_bias():
            # one-token steps mask via the rank-1 <=pos key bias
            bias = helper.create_variable_for_type_inference("float32")
            helper.append_op(
                "decode_pos_mask", inputs={"Pos": [cache["pos"]]},
                outputs={"Out": [bias]}, attrs={"t_max": t_max, "batch": bsz},
            )
            return bias

        if ragged:
            # RAGGED step: every row carries its own global query base
            # (pos_rows), so the offset-causal mask is per-row — one
            # dispatch mixes prefill chunks with one-token decodes.  GQA
            # tiles K/V back to n_head (same accepted tradeoff as the
            # chunked step: per-row cutoffs cannot share the time axis
            # with the query-group fold).
            ctx = layers.fused_attention(
                q, repeat_kv(k_full), repeat_kv(v_full), causal=True,
                qstart=cache["pos_rows"], scale=dh ** -0.5,
            )  # [B, H, W, Dh]
        elif width > 1:
            # CHUNKED decode/prefill: W queries at global positions
            # pos..pos+W-1 against the whole cache — offset-causal
            # masking (fused_attention qstart) gives each chunk row its
            # own cutoff, so one dispatch fills W cache slots.  GQA
            # tiles K/V back to n_head here (accepted tradeoff: the
            # one-token group fold puts the g query heads on the time
            # axis, which cannot carry W per-row causal cutoffs at the
            # same time; chunked steps are compute-bound MXU work, so
            # the n_head/n_kv-fold cache read costs little where the
            # fold matters most — the HBM-bound one-token step keeps it)
            ctx = layers.fused_attention(
                q, repeat_kv(k_full), repeat_kv(v_full), causal=True,
                qstart=cache["pos"], scale=dh ** -0.5,
            )  # [B, H, W, Dh]
        elif n_kv == n_head:
            ctx = layers.fused_attention(
                q, k_full, v_full, bias=pos_bias(), causal=False,
                scale=dh ** -0.5,
            )  # [B, H, 1, Dh]
        else:
            # GQA decode WITHOUT tiling K/V back to n_head: the g query
            # heads of a group all attend the same kv head, so fold the
            # group onto the (length-1) query-time axis — heads = n_kv,
            # Tq = g.  The rank-1 key bias broadcasts over the g rows;
            # per-step K/V reads really are n_kv-sized.
            g = n_head // n_kv
            q_g = layers.reshape(q, [bsz, n_kv, g, dh])
            ctx = layers.fused_attention(
                q_g, k_full, v_full, bias=pos_bias(), causal=False,
                scale=dh ** -0.5,
            )  # [B, n_kv, g, Dh]
            ctx = layers.reshape(ctx, [bsz, n_head, 1, dh])
    elif fused:
        if attn_bias is not None and kpad_bias is None:
            raise ValueError(
                "fused attention cannot consume the dense [B,H,Tq,Tk] "
                "attn_bias — pass its rank-1 key-padding row as kpad_bias "
                "(plus causal=True for decoder self-attention) or use "
                "fused=False"
            )
        k, v = repeat_kv(k), repeat_kv(v)
        with scoped("core"):
            ctx = layers.fused_attention(
                q, k, v, bias=kpad_bias, causal=causal, scale=dh ** -0.5,
                window=window,
            )  # [B, H, Tq, Dh]
    else:
        k, v = repeat_kv(k), repeat_kv(v)
        product = layers.matmul(q, k, transpose_y=True, alpha=dh ** -0.5)
        if attn_bias is not None:
            product = layers.elementwise_add(product, attn_bias)
        weights = layers.softmax(product)
        if dropout_rate:
            weights = layers.dropout(weights, dropout_rate, is_test=is_test)
        ctx = layers.matmul(weights, v)  # [B, H, Tq, Dh]
    ctx = layers.transpose(ctx, [0, 2, 1, 3])
    b, t = ctx.shape[0], ctx.shape[1]
    joined = [b, t, n_head * dh]
    if gate is None:
        ctx = layers.reshape(ctx, joined)
    elif out_gate == "head":  # [B, T, H, Dh] x [B, T, H, 1], then joined
        with scoped("attn_gate"):
            ctx = layers.elementwise_mul(
                ctx, layers.unsqueeze(layers.sigmoid(gate), [3]))
        ctx = layers.reshape(ctx, joined)
    else:  # a gate a lane meets the heads joined
        ctx = layers.reshape(ctx, joined)
        with scoped("attn_gate"):
            ctx = layers.elementwise_mul(ctx, layers.sigmoid(gate))
    return layers.fc(ctx, size=d_model, num_flatten_dims=2, bias_attr=False,
                     param_attr=pa("mha_o.w"))


def latent_attention(
    x, n_head, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim, v_head_dim,
    norm_eps=1e-6, rotary_base=10000.0, rotary_interleaved=True,
    param_attr=None, rotary=True, q_lora_rank=None,
):
    """Multi-head latent attention (MLA, DeepSeek-V2/V3), the causal
    training path, beside `multi_head_attention`: keys and values are not
    projected from x head by head but expanded from one `kv_lora_rank`-wide
    latent that all heads share, and position goes through a decoupled
    `qk_rope_head_dim`-wide rotary part, of which the KEY has one for all
    heads.  Per token, h = x_t:

      q            = h W_q                 -> [H, nope + rope]
      [c, k_rot]   = h W_kv_a              -> [kv_lora_rank], [rope]
      [k_nope, v]  = rms(c; own gain) W_kv_b -> [H, nope], [H, v_head_dim]
      RoPE on q's last `rope` and on k_rot (`rotary_interleaved`: the
      published (2i, 2i+1) pairing, see `layers.rotary_embed`)
      o = softmax([q_nope, q_rot] [k_nope, k_rot]^T (nope + rope)^-0.5,
                  causal) v                -> [H, v_head_dim]
      out = concat(o) W_o

    The scores are nope + rope wide and the values v_head_dim: one
    `fused_attention` op takes both (the flash kernel at (192, 128) on the
    chip, ops/nn_ops._flash_engages; dense XLA elsewhere).  No bias, no
    dropout, no cache: serving keeps the latent and the rotary key instead
    of K and V, which is another path.
    `q_lora_rank` (the configurations that publish one) puts a latent
    under the query as well: q = rms(h W_q_a; own gain) W_q_b, with W_q_a
    [d, q_lora_rank] and W_q_b [q_lora_rank, H (nope + rope)] in W_q's
    place; everything after q is the same.  None builds no such op.
    `rotary=False` (Kimi Linear's `mla_use_nope`) is the same layer without
    any position encoding: q's `rope`-wide part and the shared key part go
    into the scores as projected, the key part still ONE for all heads,
    repeated and concatenated; q is then never split.

    Built under `name_scope("mla")` with inner scopes `q_latent` (where
    there is a query latent: its two projections and its norm), `down`
    (the projections from x and the latent's norm), `up` (the expansion of
    the latent), `rope`, `core` (the fused_attention op) and `out`, so the
    lowered HLO carries them.  param_attr as `multi_head_attention`'s."""
    pa = param_attr or named
    d_model = int(x.shape[-1])
    b, t = x.shape[0], x.shape[1]
    d_qk = qk_nope_head_dim + qk_rope_head_dim

    def heads(y, width):  # [B, T, H * width] -> [B, H, T, width]
        return layers.transpose(
            layers.reshape(y, [b, t, n_head, width]), [0, 2, 1, 3])

    with framework.name_scope("mla"):
        if q_lora_rank is not None:
            with framework.name_scope("q_latent"):
                c_q = layers.fc(x, size=q_lora_rank, num_flatten_dims=2,
                                bias_attr=False, param_attr=pa("mla_q_a.w"))
                c_q = layers.rms_norm(c_q, epsilon=norm_eps,
                                      param_attr=pa("mla_q_a_norm.w"))
                q = layers.fc(c_q, size=n_head * d_qk, num_flatten_dims=2,
                              bias_attr=False, param_attr=pa("mla_q_b.w"))
        with framework.name_scope("down"):
            if q_lora_rank is None:
                q = layers.fc(x, size=n_head * d_qk, num_flatten_dims=2,
                              bias_attr=False, param_attr=pa("mla_q.w"))
            latent = layers.fc(x, size=kv_lora_rank + qk_rope_head_dim,
                               num_flatten_dims=2, bias_attr=False,
                               param_attr=pa("mla_kv_a.w"))
            c, k_rot = layers.split(
                latent, [kv_lora_rank, qk_rope_head_dim], dim=-1)
            c = layers.rms_norm(c, epsilon=norm_eps,
                                param_attr=pa("mla_kv_a_norm.w"))
        with framework.name_scope("up"):
            kv = layers.fc(c, size=n_head * (qk_nope_head_dim + v_head_dim),
                           num_flatten_dims=2, bias_attr=False,
                           param_attr=pa("mla_kv_b.w"))
            k_nope, v = layers.split(
                heads(kv, qk_nope_head_dim + v_head_dim),
                [qk_nope_head_dim, v_head_dim], dim=-1)
        with framework.name_scope("rope"):
            q = heads(q, d_qk)
            if rotary:
                q_nope, q_rot = layers.split(
                    q, [qk_nope_head_dim, qk_rope_head_dim], dim=-1)
                q_rot = layers.rotary_embed(q_rot, base=rotary_base,
                                            interleaved=rotary_interleaved)
            k_rot = layers.reshape(k_rot, [b, 1, t, qk_rope_head_dim])
            if rotary:
                # one rotary key for all heads, rotated once, then repeated
                k_rot = layers.rotary_embed(k_rot, base=rotary_base,
                                            interleaved=rotary_interleaved)
            k_rot = layers.expand(k_rot, [1, n_head, 1, 1])
            if rotary:
                q = layers.concat([q_nope, q_rot], axis=3)
            k = layers.concat([k_nope, k_rot], axis=3)
        with framework.name_scope("core"):
            ctx = layers.fused_attention(q, k, v, causal=True,
                                         scale=d_qk ** -0.5)
        with framework.name_scope("out"):
            ctx = layers.reshape(layers.transpose(ctx, [0, 2, 1, 3]),
                                 [b, t, n_head * v_head_dim])
            return layers.fc(ctx, size=d_model, num_flatten_dims=2,
                             bias_attr=False, param_attr=pa("mla_o.w"))


def positionwise_ffn(x, d_inner, d_model, dropout_rate=0.0, is_test=False):
    hidden = layers.fc(x, size=d_inner, num_flatten_dims=2, act="relu",
                       param_attr=named("ffn_in.w"),
                       bias_attr=named("ffn_in.b"))
    if dropout_rate:
        hidden = layers.dropout(hidden, dropout_rate, is_test=is_test)
    return layers.fc(hidden, size=d_model, num_flatten_dims=2,
                     param_attr=named("ffn_out.w"))


def pre_post_process(prev, out, dropout_rate=0.0, is_test=False):
    """residual add + layer_norm (the reference's post_process_layer 'dan')."""
    if dropout_rate:
        out = layers.dropout(out, dropout_rate, is_test=is_test)
    added = layers.elementwise_add(prev, out)
    return layers.layer_norm(added, begin_norm_axis=2)


def encoder_layer(x, attn_bias, hp, is_test=False, kpad_bias=None):
    fused = getattr(hp, "fused_attn", False)
    attn = multi_head_attention(
        x, x, x, attn_bias, hp.d_model, hp.n_head, hp.dropout, is_test,
        fused=fused, kpad_bias=kpad_bias,
    )
    x = pre_post_process(x, attn, hp.dropout, is_test)
    ffn = positionwise_ffn(x, hp.d_inner_hid, hp.d_model, hp.dropout, is_test)
    return pre_post_process(x, ffn, hp.dropout, is_test)


def decoder_layer(x, enc_out, self_bias, cross_bias, hp, is_test=False,
                  self_kpad=None, cross_kpad=None, cache=None):
    """With `cache` ({"k","v","pos"}), x is ONE current target token:
    self-attention runs KV-cached (same machinery as gpt2's decode step)
    and cross-attention attends the full enc_out with a one-token query.
    The SAME function builds training and decode-step graphs, so the
    parameter-creation order (weight sharing by name) holds by
    construction."""
    fused = getattr(hp, "fused_attn", False)
    self_attn = multi_head_attention(
        x, x, x, self_bias if cache is None else None, hp.d_model,
        hp.n_head, 0.0 if cache is not None else hp.dropout, is_test,
        fused=fused or cache is not None,
        kpad_bias=self_kpad if cache is None else None,
        causal=fused and cache is None, cache=cache,
    )
    x = pre_post_process(x, self_attn, hp.dropout, is_test)
    cross = multi_head_attention(
        x, enc_out, enc_out, cross_bias, hp.d_model, hp.n_head,
        0.0 if cache is not None else hp.dropout, is_test,
        fused=fused or cache is not None, kpad_bias=cross_kpad,
    )
    x = pre_post_process(x, cross, hp.dropout, is_test)
    ffn = positionwise_ffn(x, hp.d_inner_hid, hp.d_model, hp.dropout, is_test)
    return pre_post_process(x, ffn, hp.dropout, is_test)


def transformer(
    src_ids, trg_ids, src_slf_attn_bias, trg_slf_attn_bias, trg_src_attn_bias,
    hp=ModelHyperParams, is_test=False, trg_kpad_bias=None
):
    """Full encoder-decoder; returns [B, Tt, trg_vocab] logits.

    When hp.fused_attn is set, attention runs through the fused_attention
    op: the rank-1 key-padding rows are derived in-graph from the
    [B, 1, 1, Tk] bias feeds (same feed contract), and decoder causality
    comes from the kernel's causal flag instead of the dense
    trg_slf_attn_bias — which requires trg_kpad_bias ([B, Tt], e.g. built
    from the token-weight feed) since the dense [B, 1, Tt, Tt] bias cannot
    be passed rank-1."""
    fused = getattr(hp, "fused_attn", False)
    src_kpad = cross_kpad = None
    if fused:
        src_len = int(src_slf_attn_bias.shape[-1])
        src_kpad = layers.reshape(src_slf_attn_bias, [-1, src_len])
        cross_kpad = layers.reshape(trg_src_attn_bias, [-1, src_len])
        if trg_kpad_bias is None:
            raise ValueError("hp.fused_attn requires trg_kpad_bias")
    enc_in = prepare_embedding(
        src_ids, hp.src_vocab_size, hp.d_model, hp.max_length, hp.dropout,
        "src_pos_enc_table", is_test,
    )
    remat = getattr(hp, "recompute", False) and not is_test
    x = enc_in
    for _ in range(hp.n_layer):
        if remat:
            x = layers.recompute(
                lambda h: encoder_layer(h, src_slf_attn_bias, hp, is_test,
                                        kpad_bias=src_kpad), x)
        else:
            x = encoder_layer(x, src_slf_attn_bias, hp, is_test,
                              kpad_bias=src_kpad)
    enc_out = x

    dec_in = prepare_embedding(
        trg_ids, hp.trg_vocab_size, hp.d_model, hp.max_length, hp.dropout,
        "trg_pos_enc_table", is_test,
    )
    y = dec_in
    for _ in range(hp.n_layer):
        if remat:
            y = layers.recompute(
                lambda h: decoder_layer(
                    h, enc_out, trg_slf_attn_bias, trg_src_attn_bias, hp,
                    is_test, self_kpad=trg_kpad_bias, cross_kpad=cross_kpad),
                y)
        else:
            y = decoder_layer(
                y, enc_out, trg_slf_attn_bias, trg_src_attn_bias, hp, is_test,
                self_kpad=trg_kpad_bias, cross_kpad=cross_kpad,
            )

    logits = layers.fc(y, size=hp.trg_vocab_size, num_flatten_dims=2,
                       bias_attr=False, param_attr=named("softmax_out.w"))
    return logits


def wmt_transformer_program(hp=ModelHyperParams, src_len=64, trg_len=64, learning_rate=2.0, warmup_steps=4000, is_test=False, use_bf16=False, mesh=None):
    """Build (main, startup, feed names, [loss]) for training — the analog of
    the reference's transformer train program w/ label smoothing + noam lr.

    use_bf16 applies the AMP rewrite (bf16 matmuls on the MXU, f32 master
    weights) before minimize so grads differentiate through the casts.
    hp.fused_attn additionally routes attention through the fused op; the
    decoder key-padding row is derived in-graph from the lbl_weight feed
    (weight 1 = real token), so the feed contract is unchanged."""
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        src = layers.data("src_word", shape=[src_len], dtype="int64")
        trg = layers.data("trg_word", shape=[trg_len], dtype="int64")
        lbl = layers.data("lbl_word", shape=[trg_len], dtype="int64")
        src_bias = layers.data("src_slf_attn_bias", shape=[1, 1, src_len], dtype="float32")
        trg_bias = layers.data("trg_slf_attn_bias", shape=[1, trg_len, trg_len], dtype="float32")
        cross_bias = layers.data("trg_src_attn_bias", shape=[1, 1, src_len], dtype="float32")
        weights = layers.data("lbl_weight", shape=[trg_len], dtype="float32")

        trg_kpad = None
        if getattr(hp, "fused_attn", False):
            # weight w ∈ {0,1} -> bias 0 at real tokens, -1e9 at padding
            trg_kpad = layers.scale(weights, scale=1e9, bias=-1e9)
            trg_kpad.stop_gradient = True
        logits = transformer(src, trg, src_bias, trg_bias, cross_bias, hp,
                             is_test, trg_kpad_bias=trg_kpad)
        label_oh = layers.one_hot(lbl, hp.trg_vocab_size)
        if hp.label_smooth_eps:
            label_oh = layers.label_smooth(label_oh, epsilon=hp.label_smooth_eps)
        cost = layers.softmax_with_cross_entropy(logits, label_oh, soft_label=True)
        weighted = layers.elementwise_mul(cost, layers.unsqueeze(weights, [2]))
        sum_cost = layers.reduce_sum(weighted)
        token_count = layers.reduce_sum(weights)
        avg_cost = layers.elementwise_div(sum_cost, token_count)

        # fold the one_hot -> label_smooth -> soft-label-xent chain into
        # the closed-form smooth_label_xent op: at bench config the chain
        # materializes three [B*T, V] f32 arrays (~4 GB/step) for a
        # quantity computable from logits + int labels alone
        from ..transpiler.pass_registry import apply_pass

        apply_pass(main, "smooth_label_xent_fuse_pass")
        # then fold the [H, V] projection INTO the loss (logits-free
        # fused cross-entropy: fused_linear_xent lowers to
        # linear_xent_tiled, the [B, T, V] f32 logits a vocabulary tile
        # at a time) and collapse the FFN mul+bias+act /
        # residual-add+layer_norm chains into fc / fused_residual_ln
        # (one dense lowering each, their epilogues fused by XLA)
        apply_pass(main, "linear_xent_fuse_pass")
        apply_pass(main, "matmul_epilogue_fuse_pass")
        # and the heads' transposes around each fused attention into the
        # op: it takes the projections' [B, T, H, d] as they are written
        # (layout "bthd"), and the one-tile kernel reads them in place
        # where nn_ops._in_place_engages says so
        apply_pass(main, "attention_layout_fuse_pass")

        if use_bf16:
            # AMP rides the pass registry (bf16 MXU compute, f32 master
            # params — the optimizer state and param vars stay f32)
            apply_pass(main, "bf16_amp_pass")
        # HBM-budgeted rematerialization (FLAGS_hbm_budget_bytes): after
        # the fuse/AMP rewrites (segments carry the final op mix), before
        # minimize (grads differentiate through the recompute ops)
        from ..transpiler.remat import maybe_remat

        maybe_remat(main, avg_cost, is_test, mesh=mesh)
        if not is_test:
            lr = layers.learning_rate_scheduler.noam_decay(hp.d_model, warmup_steps)
            lr = layers.scale(lr, scale=float(learning_rate))
            opt = fluid.optimizer.Adam(learning_rate=lr, beta1=0.9, beta2=0.997, epsilon=1e-9)
            opt.minimize(avg_cost)
    if mesh is not None:
        # GSPMD training stamp: transformer-family rules lifted to
        # training names (grads + Adam moments shard like their param),
        # batch feeds over the mesh's dp axis — no model edits
        from ..parallel.partition_rules import (annotate_spmd,
                                                train_partition_rules_for)

        annotate_spmd(main, mesh, train_partition_rules_for(
            getattr(hp, "partition_family", "transformer")))
    feeds = [
        "src_word", "trg_word", "lbl_word", "src_slf_attn_bias",
        "trg_slf_attn_bias", "trg_src_attn_bias", "lbl_weight",
    ]
    return main, startup, feeds, [avg_cost, token_count]


NEG_BIAS = -1e9  # the shared "masked" sentinel across train/infer masks


def pad_bias(lens, max_len):
    """[B] lengths -> [B, 1, 1, max_len] additive key-padding bias."""
    lens = np.asarray(lens).reshape(-1)
    pad = np.arange(max_len)[None, :] >= lens[:, None]
    return np.where(pad, NEG_BIAS, 0.0).astype("float32")[:, None, None, :]


def causal_plus_pad_bias(lens, max_len):
    """[B] lengths -> [B, 1, T, T] causal + key-padding decoder bias."""
    lens = np.asarray(lens).reshape(-1)
    causal = np.triu(np.ones((max_len, max_len)), k=1) * NEG_BIAS
    pad = np.arange(max_len)[None, :] >= lens[:, None]
    bias = np.where(pad[:, None, :], NEG_BIAS, 0.0) + causal[None, :, :]
    return bias[:, None, :, :].astype("float32")


def make_fake_batch(batch_size, src_len, trg_len, hp=ModelHyperParams, seed=0):
    """Synthetic padded batch + masks (host-side; analog of the data reader)."""
    rng = np.random.RandomState(seed)
    src = rng.randint(1, hp.src_vocab_size, (batch_size, src_len)).astype("int64")
    trg = rng.randint(1, hp.trg_vocab_size, (batch_size, trg_len)).astype("int64")
    lbl = rng.randint(1, hp.trg_vocab_size, (batch_size, trg_len)).astype("int64")
    src_lens = rng.randint(src_len // 2, src_len + 1, (batch_size,))
    trg_lens = rng.randint(trg_len // 2, trg_len + 1, (batch_size,))

    src_bias = pad_bias(src_lens, src_len)
    trg_bias = causal_plus_pad_bias(trg_lens, trg_len)
    cross_bias = pad_bias(src_lens, src_len)
    weights = (np.arange(trg_len)[None, :] < trg_lens[:, None]).astype("float32")
    return {
        "src_word": src,
        "trg_word": trg,
        "lbl_word": lbl,
        "src_slf_attn_bias": src_bias,
        "trg_slf_attn_bias": trg_bias,
        "trg_src_attn_bias": cross_bias,
        "lbl_weight": weights,
    }


def transformer_logits_program(hp=ModelHyperParams, src_len=64, trg_len=64):
    """Inference program fetching [B, Tt, trg_vocab] logits — the
    greedy/beam decode-step workhorse (static shapes, one compile).
    Built under unique_name.guard() so it shares weights by name with a
    wmt_transformer_program trained earlier in the same scope."""
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        src = layers.data("src_word", shape=[src_len], dtype="int64")
        trg = layers.data("trg_word", shape=[trg_len], dtype="int64")
        src_bias = layers.data("src_slf_attn_bias", shape=[1, 1, src_len], dtype="float32")
        trg_bias = layers.data("trg_slf_attn_bias", shape=[1, trg_len, trg_len], dtype="float32")
        cross_bias = layers.data("trg_src_attn_bias", shape=[1, 1, src_len], dtype="float32")
        trg_kpad = None
        if getattr(hp, "fused_attn", False):
            # the dense decoder bias's LAST causal row is pure key-padding
            # (causal contributes 0 there): extract it as the rank-1 bias
            # the fused path needs
            last_row = layers.slice(
                trg_bias, axes=[2], starts=[trg_len - 1], ends=[trg_len]
            )
            trg_kpad = layers.reshape(last_row, [-1, trg_len])
            trg_kpad.stop_gradient = True
        logits = transformer(src, trg, src_bias, trg_bias, cross_bias, hp,
                             is_test=True, trg_kpad_bias=trg_kpad)
    feeds = ["src_word", "trg_word", "src_slf_attn_bias",
             "trg_slf_attn_bias", "trg_src_attn_bias"]
    return main, startup, feeds, [logits]


def _translate_prologue(main, src_ids, src_lens, max_out_len):
    """Shared decode prologue: program widths, src validation, padding bias."""
    blk = main.global_block()
    src_len = int(blk.vars["src_word"].shape[1])
    trg_len = int(blk.vars["trg_word"].shape[1])
    max_out_len = min(max_out_len or trg_len, trg_len)
    src_ids = np.asarray(src_ids, "int64")
    b, p = src_ids.shape
    assert p == src_len, "src must be padded to the program's %d" % src_len
    src_lens = np.asarray(src_lens).reshape(-1)
    return src_ids, src_lens, pad_bias(src_lens, src_len), trg_len, max_out_len, b


def greedy_translate(exe, main, fetches, src_ids, src_lens, bos_id, eos_id,
                     max_out_len=None, pad_id=0):
    """Greedy decoding on a fixed-shape logits program (the reference
    transformer's inference role, TPU-style: static shapes, one compile;
    causal masking hides the padded target tail each step).

    src_ids [B, Ts] int64, src_lens [B] — returns [B, T_out] int64 rows
    starting with bos_id; generation stops early once every row emitted
    eos_id."""
    src_ids, src_lens, src_bias, trg_len, max_out_len, b = _translate_prologue(
        main, src_ids, src_lens, max_out_len
    )
    trg = np.full((b, trg_len), pad_id, "int64")
    trg[:, 0] = bos_id
    done = np.zeros(b, bool)
    cur = 1
    while cur < max_out_len and not done.all():
        trg_bias = causal_plus_pad_bias(np.full(b, cur), trg_len)
        feed = {
            "src_word": src_ids,
            "trg_word": trg,
            "src_slf_attn_bias": src_bias,
            "trg_slf_attn_bias": trg_bias,
            "trg_src_attn_bias": src_bias,
        }
        (logits,) = exe.run(main, feed=feed, fetch_list=fetches)
        nxt = np.asarray(logits)[:, cur - 1, :].argmax(axis=-1)
        nxt = np.where(done, pad_id, nxt)
        trg[:, cur] = nxt
        done |= nxt == eos_id
        cur += 1
    return trg[:, :cur]


def beam_translate(exe, main, fetches, src_ids, src_lens, bos_id, eos_id,
                   beam_size=4, max_out_len=None, pad_id=0,
                   length_penalty=0.0):
    """Beam-search decoding on the transformer_logits_program (same feed
    contract as greedy_translate).  Returns (ids [B, T_out], scores [B])."""
    from ..contrib.decoder.beam_search_decoder import full_sequence_beam_search

    src_ids, src_lens, src_bias, trg_len, max_out_len, b = _translate_prologue(
        main, src_ids, src_lens, max_out_len
    )
    src_rep = np.repeat(src_ids, beam_size, axis=0)
    src_bias_rep = np.repeat(src_bias, beam_size, axis=0)

    trg0 = np.full((b, trg_len), pad_id, "int64")
    trg0[:, 0] = bos_id

    def logits_fn(rows, cur):
        feed = {
            "src_word": src_rep,
            "trg_word": rows,
            "src_slf_attn_bias": src_bias_rep,
            "trg_slf_attn_bias": causal_plus_pad_bias(
                np.full(rows.shape[0], cur), trg_len
            ),
            "trg_src_attn_bias": src_bias_rep,
        }
        (logits,) = exe.run(main, feed=feed, fetch_list=fetches)
        return np.asarray(logits)[:, cur - 1, :]

    return full_sequence_beam_search(
        logits_fn, trg0, 1, beam_size, max_out_len, eos_id, pad_id,
        length_penalty,
    )


def transformer_decode_programs(hp=ModelHyperParams, batch=1, src_len=64,
                                t_max=None, width=1):
    """KV-cached seq2seq decoding, split into two programs sharing
    persistable state (and weight names with wmt_transformer_program /
    transformer_logits_program built in the same process):

      enc_main:  feeds src_word [B, Ts] + src_slf_attn_bias [B,1,1,Ts];
                 runs the encoder ONCE, persisting enc_out and the
                 cross-attention key-padding row as scope state.
      step_main: feeds trg_tok [B, W] + pos [1] (+ pos_vec [W] when
                 width W > 1); one cached decoder step (self-attention
                 over per-layer K/V caches — offset-causal for W > 1 —
                 and W-query cross-attention over the persisted
                 enc_out); fetches logits [B, trg_vocab] (W == 1) or
                 [B, W, trg_vocab].
      cache_startup: zeroes all the persistable decode state.

    Per generated token this is O((t_max + src_len) d) work instead of
    the full re-decode's O(t_max^2 d); width > 1 scores W known target
    positions per dispatch — the candidate-RESCORING workhorse (force-
    decode a hypothesis in ceil(T/W) MXU-shaped dispatches).  Returns
    (enc_main, step_main, cache_startup, enc_feeds, step_feeds,
    enc_fetch, step_fetch)."""
    import paddle_tpu as fluid

    t_max = t_max or hp.max_length
    assert t_max <= hp.max_length, (
        "t_max %d exceeds hp.max_length %d" % (t_max, hp.max_length))
    width = int(width)
    assert 1 <= width <= t_max, (width, t_max)
    dh = hp.d_model // hp.n_head
    enc_main = fluid.Program()
    step_main = fluid.Program()
    cache_startup = fluid.Program()
    throwaway = fluid.Program()

    with unique_name.guard():
        # ---- encoder program (parameter names: src emb + enc layers) ----
        with fluid.program_guard(enc_main, throwaway):
            src = layers.data("src_word", shape=[batch, src_len],
                              dtype="int64", append_batch_size=False)
            src_bias = layers.data(
                "src_slf_attn_bias", shape=[batch, 1, 1, src_len],
                dtype="float32", append_batch_size=False)
            src_kpad = layers.reshape(src_bias, [-1, src_len])
            x = prepare_embedding(
                src, hp.src_vocab_size, hp.d_model, hp.max_length, 0.0,
                "src_pos_enc_table", is_test=True)
            for _ in range(hp.n_layer):
                x = encoder_layer(x, src_bias, hp, is_test=True,
                                  kpad_bias=src_kpad)
            eb = enc_main.global_block()
            enc_cache = eb.create_var(
                name="tfm_enc_out_cache", shape=[batch, src_len, hp.d_model],
                dtype="float32", persistable=True)
            kpad_cache = eb.create_var(
                name="tfm_cross_kpad_cache", shape=[batch, src_len],
                dtype="float32", persistable=True)
            eb.append_op("assign", inputs={"X": [x]},
                         outputs={"Out": [enc_cache]})
            eb.append_op("assign", inputs={"X": [src_kpad]},
                         outputs={"Out": [kpad_cache]})

        # ---- decode-step program (names continue: trg emb + dec layers) --
        with fluid.program_guard(step_main, throwaway):
            tok = layers.data("trg_tok", shape=[batch, width], dtype="int64",
                              append_batch_size=False)
            pos = layers.data("pos", shape=[1], dtype="int64",
                              append_batch_size=False)
            pos_vec = None
            if width > 1:
                pos_vec = layers.data("pos_vec", shape=[width],
                                      dtype="int64",
                                      append_batch_size=False)
            word = layers.embedding(
                tok, size=[hp.trg_vocab_size, hp.d_model],
                param_attr=_word_emb_attr(hp.d_model),
            )  # [B, W, D] (W == 1 squeezes in the lookup)
            word = layers.scale(
                layers.reshape(word, shape=[batch, width, hp.d_model]),
                scale=hp.d_model ** 0.5)
            pos_table = layers.create_parameter(
                shape=[hp.max_length, hp.d_model], dtype="float32",
                name="trg_pos_enc_table",
                attr=ParamAttr(
                    name="trg_pos_enc_table", trainable=False,
                    initializer=_NumpyInit(
                        _pos_encoding_table(hp.max_length, hp.d_model))),
            )
            if width == 1:
                pos_row = layers.reshape(layers.gather(pos_table, pos),
                                         shape=[1, 1, hp.d_model])
                y = layers.elementwise_add(word, pos_row)
            else:
                pos_rows = layers.gather(pos_table, pos_vec)  # [W, D]
                y = layers.elementwise_add(word, pos_rows, axis=1)
            sb = step_main.global_block()
            enc_ref = sb.create_var(
                name="tfm_enc_out_cache", shape=[batch, src_len, hp.d_model],
                dtype="float32", persistable=True)
            kpad_ref = sb.create_var(
                name="tfm_cross_kpad_cache", shape=[batch, src_len],
                dtype="float32", persistable=True)
            from .decode_cache import create_kv_caches

            cache_names = ["tfm_enc_out_cache", "tfm_cross_kpad_cache"]
            kv_caches, kv_names = create_kv_caches(
                sb, "tfm", hp.n_layer, batch, hp.n_head, t_max, dh)
            cache_names += kv_names
            for cache in kv_caches:
                cache["pos"] = pos
                if pos_vec is not None:
                    cache["pos_vec"] = pos_vec
                y = decoder_layer(y, enc_ref, None, None, hp, is_test=True,
                                  cross_kpad=kpad_ref, cache=cache)
            logits = layers.fc(y, size=hp.trg_vocab_size, num_flatten_dims=2,
                               bias_attr=False,
                               param_attr=named("softmax_out.w"))
            if width == 1:
                logits = layers.reshape(logits,
                                        shape=[batch, hp.trg_vocab_size])

        # ---- cache zeroing program --------------------------------------
        from .decode_cache import add_cache_zero_fills

        add_cache_zero_fills(cache_startup, [
            (cname, (enc_main.global_block()._find_var_recursive(cname)
                     or step_main.global_block()._find_var_recursive(cname)
                     ).shape)
            for cname in cache_names])

    step_feeds = ["trg_tok", "pos"] + (["pos_vec"] if width > 1 else [])
    return (enc_main, step_main, cache_startup,
            ["src_word", "src_slf_attn_bias"], step_feeds,
            ["tfm_enc_out_cache"], [logits])


def force_decode_logits_cached(exe, programs, src_ids, src_lens, trg_ids):
    """Teacher-forced scoring through the cached decoder: run the
    encoder once, then feed the GIVEN target tokens in ceil(T/W)
    width-W dispatches (programs from transformer_decode_programs
    (width=W)); returns [B, T, V] logits where row t is the
    next-token distribution after trg_ids[:, t] — the candidate-
    RESCORING workhorse (log-prob of a hypothesis without a token
    loop).  The last chunk re-anchors inside the cache bound
    (rewriting identical slots is idempotent)."""
    from .decode_cache import probe_cache_len

    (enc_main, step_main, cache_startup, _enc_feeds, step_feeds,
     _enc_fetch, step_fetch) = programs
    src_ids = np.asarray(src_ids, "int64")
    trg_ids = np.asarray(trg_ids, "int64")
    b, T = trg_ids.shape
    sb = step_main.global_block()
    step_b, width = (int(sb.vars["trg_tok"].shape[0]),
                     int(sb.vars["trg_tok"].shape[1]))
    assert b == step_b, (b, step_b)
    t_max = probe_cache_len(step_main, "tfm")
    assert T <= t_max, (T, t_max)
    src_lens = np.asarray(src_lens).reshape(-1)

    exe.run(cache_startup)
    exe.run(enc_main, feed={
        "src_word": src_ids,
        "src_slf_attn_bias": pad_bias(src_lens, src_ids.shape[1]),
    }, fetch_list=[])

    from .decode_cache import run_chunked_ids

    out = None
    for c0, lg in run_chunked_ids(exe, step_main, step_fetch, trg_ids,
                                  width, t_max, "trg_tok",
                                  has_pos_vec="pos_vec" in step_feeds):
        lg = lg.reshape(b, width, -1)
        if out is None:
            out = np.zeros((b, T, lg.shape[-1]), lg.dtype)
        hi = min(c0 + width, T)
        out[:, c0:hi] = lg[:, :hi - c0]
    return out


def _translate_cached_loop(exe, programs, src_ids, src_lens, bos_id,
                           eos_id, max_out_len, pad_id, pick_fn):
    """Shared driver for cached seq2seq decoding: validate, zero caches,
    run the encoder once, then step the cached decoder; pick_fn(logits
    [B, V]) -> [B] chooses each next token (argmax or sampler)."""
    from .decode_cache import probe_cache_len

    (enc_main, step_main, cache_startup, enc_feeds, step_feeds,
     enc_fetch, step_fetch) = programs
    src_ids = np.asarray(src_ids, "int64")
    b, _ = src_ids.shape
    sb = step_main.global_block()
    step_b = int(sb.vars["trg_tok"].shape[0])
    assert b == step_b, (
        "src batch %d != decode programs' static batch %d" % (b, step_b))
    t_max = probe_cache_len(step_main, "tfm")
    max_out_len = min(max_out_len or t_max, t_max)
    src_lens = np.asarray(src_lens).reshape(-1)

    exe.run(cache_startup)
    # no fetch: the encoder's persistable writes survive DCE, and fetching
    # the [B, Ts, D] activation would be a pure wasted D2H transfer
    exe.run(enc_main, feed={
        "src_word": src_ids,
        "src_slf_attn_bias": pad_bias(src_lens, src_ids.shape[1]),
    }, fetch_list=[])

    trg = np.full((b, max_out_len), pad_id, "int64")
    trg[:, 0] = bos_id
    done = np.zeros(b, bool)
    cur = 1
    while cur < max_out_len and not done.all():
        (logits,) = exe.run(step_main, feed={
            "trg_tok": trg[:, cur - 1:cur],
            "pos": np.array([cur - 1], "int64"),
        }, fetch_list=step_fetch)
        nxt = np.where(done, pad_id, pick_fn(logits))
        trg[:, cur] = nxt
        done |= nxt == eos_id
        cur += 1
    return trg[:, :cur]


def greedy_translate_cached(exe, programs, src_ids, src_lens, bos_id, eos_id,
                            max_out_len=None, pad_id=0):
    """Greedy decoding through the KV-cached decode programs (the output
    contract of greedy_translate, at O((t_max + Ts) d) per token).
    `programs` is transformer_decode_programs' return tuple."""
    return _translate_cached_loop(
        exe, programs, src_ids, src_lens, bos_id, eos_id, max_out_len,
        pad_id, lambda lg: np.asarray(lg).argmax(axis=-1).astype("int64"))


def beam_translate_cached(exe, programs, src_ids, src_lens, bos_id, eos_id,
                          beam_size=4, max_out_len=None, pad_id=0,
                          length_penalty=0.0):
    """Beam-search decoding through the KV-cached decode programs (built
    with batch = B * beam_size).  Self-attention caches shuffle to the
    surviving beams each step; the encoder state is beam-replicated at
    encode time and invariant under the shuffle.  Output contract of
    beam_translate.  Returns (ids [B, T_out], scores [B])."""
    from ..contrib.decoder.beam_search_decoder import incremental_beam_search
    from .decode_cache import make_cache_reorder_program, probe_cache_len

    (enc_main, step_main, cache_startup, enc_feeds, step_feeds,
     enc_fetch, step_fetch) = programs
    src_ids = np.asarray(src_ids, "int64")
    b, _ = src_ids.shape
    sb = step_main.global_block()
    r = int(sb.vars["trg_tok"].shape[0])
    assert r == b * beam_size, (
        "decode programs' batch %d != src batch %d * beam %d"
        % (r, b, beam_size))
    t_max = probe_cache_len(step_main, "tfm")
    max_out_len = min(max_out_len or t_max, t_max)
    src_lens = np.asarray(src_lens).reshape(-1)

    exe.run(cache_startup)
    exe.run(enc_main, feed={
        "src_word": np.repeat(src_ids, beam_size, axis=0),
        "src_slf_attn_bias": np.repeat(
            pad_bias(src_lens, src_ids.shape[1]), beam_size, axis=0),
    }, fetch_list=[])

    # only the per-layer self-attention caches follow the beams
    reorder = make_cache_reorder_program(
        [(n, v.shape) for n, v in sb.vars.items()
         if n.startswith(("tfm_kcache_", "tfm_vcache_"))], r)

    bos = np.full((r, 1), bos_id, "int64")
    (first,) = exe.run(step_main, feed={
        "trg_tok": bos, "pos": np.array([0], "int64")}, fetch_list=step_fetch)

    def step_fn(tokens, pos):
        (lg,) = exe.run(step_main, feed={
            "trg_tok": tokens, "pos": np.array([pos], "int64")},
            fetch_list=step_fetch)
        return lg

    def reorder_fn(rows):
        exe.run(reorder, feed={"parents": rows.astype("int64")},
                fetch_list=[])

    prompt = np.full((b, 1), bos_id, "int64")
    return incremental_beam_search(
        step_fn, reorder_fn, first, prompt, 1, beam_size, max_out_len,
        eos_id, pad_id, length_penalty)


def sample_translate_cached(exe, programs, src_ids, src_lens, bos_id,
                            eos_id, max_out_len=None, temperature=1.0,
                            top_k=0, top_p=1.0, seed=None, pad_id=0):
    """Stochastic seq2seq decoding through the KV-cached programs:
    temperature / top-k / nucleus filtering with seeded numpy sampling
    (the sampling twin of greedy_translate_cached)."""
    from .decode_cache import sample_from_logits

    rng = np.random.RandomState(seed)
    return _translate_cached_loop(
        exe, programs, src_ids, src_lens, bos_id, eos_id, max_out_len,
        pad_id,
        lambda lg: sample_from_logits(lg, rng, temperature, top_k, top_p))
