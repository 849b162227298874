"""GPT-2-style decoder-only LM (BASELINE.json config 5: "ERNIE / GPT-2
345M (TP+DP on TPU mesh via DistributeTranspiler->GSPMD)").

Pre-LN causal transformer: x + attn(ln(x)), x + ffn(ln(x)); by default a
GELU MLP, learned positions, untied LM head — with modern-decoder
options on GPT2Config: n_kv_head (grouped-query attention), use_rotary
(RoPE instead of the position table), use_swiglu (gated SiLU FFN:
ffn_gate.w/ffn_up.w replace ffn_in.w), tie_embeddings (logits reuse
emb.w; no softmax_out.w exists).  Attention always goes through the
fused_attention op with causal=True — no [T, T] mask tensor ever exists
in the program (the op takes its flash kernel from the placed platform
and the shape, fused XLA otherwise).  Parameter names reuse the transformer TP patterns
(mha_[qkv].w / mha_o.w / ffn_in.w or ffn_gate.w+ffn_up.w / ffn_out.w /
emb.w / softmax_out.w) so `parallel.transformer_tp_rules` shards every
option combination unchanged on a {dp, mp} mesh.
"""

import numpy as np

from .. import layers, unique_name
from . import transformer as tfm
from .decoder import fc, lm_train_program, weight, xent_cost

__all__ = [
    "GPT2Config",
    "gpt2_lm",
    "gpt2_lm_program",
    "gpt2_logits_program",
    "greedy_generate",
    "greedy_generate_cached",
    "beam_generate_cached",
    "sample_generate_cached",
    "gpt2_decode_step_program",
    "gpt2_ragged_step_program",
    "prefill_cached_chunked",
    "speculative_generate_cached",
    "speculative_sample_generate_cached",
    "beam_generate",
    "make_fake_lm_batch",
]


class GPT2Config:
    """gpt2-small shape defaults (345M config: d_model=1024, n_layer=24,
    n_head=16); subclass to shrink for tests."""

    vocab_size = 50257
    n_ctx = 1024
    d_model = 768
    n_layer = 12
    n_head = 12
    n_kv_head = None  # < n_head enables grouped-query attention (MQA at 1)
    use_rotary = False  # RoPE on q/k instead of the learned position table
    use_swiglu = False  # gated SiLU FFN (2/3 width) instead of gelu MLP
    ffn_multiple_of = 1  # round the SwiGLU hidden up (128/256 aligns
    # the lane dim and keeps TP divisibility; 1 = exact 2/3 sizing)
    tie_embeddings = False  # output logits reuse emb.w (x @ emb.w^T)
    dropout = 0.1
    recompute = False  # rematerialize each block's activations in backward
    # which parallel.partition_rules family table shards this model's
    # persistables (weights AND the serving slot-pool caches) on a
    # tensor-parallel mesh — ServingEngine(mesh=...) resolves it
    partition_family = "gpt2"


def _attn(x, hp, is_test, cache=None):
    """Causal self-attention via the shared transformer block (same graph,
    same mha_* param names, one fused-path implementation to maintain).
    With `cache`, x is the single current token and causality comes from
    the cache's <=pos mask instead of the causal flag."""
    return tfm.multi_head_attention(
        x, x, x, None, hp.d_model, hp.n_head, dropout_rate=0.0,
        is_test=is_test, fused=True, causal=cache is None, cache=cache,
        n_kv_head=getattr(hp, "n_kv_head", None),
        rotary=getattr(hp, "use_rotary", False),
    )


def _block(x, hp, is_test, cache=None):
    """One decoder block — the SAME function builds the training graph and
    the KV-cached decode step, so the parameter-creation order (and with
    it, weight sharing by name) holds by construction."""
    a = _attn(layers.layer_norm(x, begin_norm_axis=2), hp, is_test, cache)
    if hp.dropout and not is_test:
        a = layers.dropout(a, hp.dropout, is_test=is_test)
    x = layers.elementwise_add(x, a)
    ln = layers.layer_norm(x, begin_norm_axis=2)
    if getattr(hp, "use_swiglu", False):
        # SwiGLU: silu(xW_g) * xW_u -> W_out, hidden at 2/3 of 4*d so
        # the parameter count matches the gelu MLP (the standard sizing)
        hid = int(4 * hp.d_model * 2 // 3)
        mult = int(getattr(hp, "ffn_multiple_of", 1) or 1)
        hid = ((hid + mult - 1) // mult) * mult
        h = layers.elementwise_mul(fc(ln, hid, "ffn_gate.w", act="swish"),
                                   fc(ln, hid, "ffn_up.w"))
    else:
        h = layers.fc(
            ln, size=4 * hp.d_model, num_flatten_dims=2, act="gelu",
            param_attr=weight("ffn_in.w"), bias_attr=weight("ffn_in.b"),
        )
    h = layers.fc(h, size=hp.d_model, num_flatten_dims=2,
                  param_attr=weight("ffn_out.w"))
    if hp.dropout and not is_test:
        h = layers.dropout(h, hp.dropout, is_test=is_test)
    return layers.elementwise_add(x, h)


def _tied_logits(x, hp, emb_name):
    """Output projection: x @ emb.w^T when tie_embeddings (saves the
    [vocab, d] output matrix and couples input/output token geometry),
    else a separate softmax_out.w."""
    if getattr(hp, "tie_embeddings", False):
        from .. import framework

        w = framework.default_main_program().global_block().var(emb_name)
        return layers.matmul(x, w, transpose_y=True)
    return fc(x, hp.vocab_size, "softmax_out.w")


def gpt2_lm(ids, hp=GPT2Config, is_test=False):
    """[B, T] token ids -> [B, T, vocab] next-token logits."""
    emb_attr = weight("emb.w")
    tok = layers.embedding(
        ids, size=[hp.vocab_size, hp.d_model], param_attr=emb_attr
    )
    if getattr(hp, "use_rotary", False):
        x = tok  # positions enter via RoPE on q/k inside attention
    else:
        pos_table = layers.create_parameter(
            shape=[hp.n_ctx, hp.d_model], dtype="float32",
            attr=weight("pos_emb.w", 0.01)
        )
        T = ids.shape[1]
        pos = layers.slice(pos_table, axes=[0], starts=[0], ends=[T])
        x = layers.elementwise_add(tok, pos, axis=1)
    if hp.dropout and not is_test:
        x = layers.dropout(x, hp.dropout, is_test=is_test)
    for _ in range(hp.n_layer):
        if getattr(hp, "recompute", False) and not is_test:
            x = layers.recompute(lambda h: _block(h, hp, is_test), x)
        else:
            x = _block(x, hp, is_test)
    x = layers.layer_norm(x, begin_norm_axis=2)
    return _tied_logits(x, hp, emb_attr.name)


def gpt2_lm_program(hp=GPT2Config, seq_len=128, lr=3e-4, is_test=False,
                    use_bf16=False, mesh=None):
    """Build (main, startup, feeds, [loss, token_count]) for causal-LM
    training.  Feeds: ids/labels [B, T] int64, loss_weight [B, T] float.

    Built under unique_name.guard(): parameter names are deterministic, so
    a logits program built later in the same process shares weights with
    this one through the scope by name (the train->generate workflow).

    `mesh` stamps the program for GSPMD tensor-parallel training: the
    gpt2-family rule table lifted to training names (grads + Adam
    moments shard like their param — ZeRO-style sharded optimizer
    state), batch feeds over the mesh's dp axis.  No model edits — the
    executor's _run_spmd path picks the stamp up."""
    return lm_train_program(
        lambda ids, labels: (xent_cost(gpt2_lm(ids, hp, is_test), labels),
                             None),
        seq_len, lr, is_test, use_bf16, mesh,
        getattr(hp, "partition_family", "gpt2"))


def make_fake_lm_batch(batch_size, seq_len, hp=GPT2Config, seed=0):
    rng = np.random.RandomState(seed)
    ids = rng.randint(1, hp.vocab_size, (batch_size, seq_len + 1)).astype("int64")
    return {
        "ids": ids[:, :-1],
        "labels": ids[:, 1:],
        "loss_weight": np.ones((batch_size, seq_len), "float32"),
    }


def gpt2_logits_program(hp=GPT2Config, seq_len=128):
    """Inference program fetching the full [B, T, vocab] logits (the
    decode-step workhorse: static shapes, one compile for any prompt
    length <= seq_len)."""
    import paddle_tpu as fluid

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup), unique_name.guard():
        ids = layers.data("ids", shape=[seq_len], dtype="int64")
        logits = gpt2_lm(ids, hp, is_test=True)
    return main, startup, ["ids"], [logits]


def gpt2_decode_step_program(hp=GPT2Config, batch=1, t_max=None, width=1,
                             cache_dtype="float32"):
    """KV-cached decode step (the incremental-decoding engine the
    reference's beam-search cache plumbing approximates):

        feeds:  step_ids [B, W] int64, pos [1] int64
                (+ pos_vec [W] int64 when W > 1: positions pos..pos+W-1)
        fetch:  next-token logits — [B, vocab] (W == 1) or
                [B, W, vocab] (W > 1; row i predicts position pos+i+1)
        state:  per-layer kcache/vcache [B, H, T_max, Dh] persistable vars

    cache_dtype="bfloat16" halves decode's dominant HBM tenant (writes
    cast in seq_cache_write; attention math promotes back to f32).
    width == 1 is the classic one-token step: O(T_max * d) per token.
    width > 1 is the CHUNKED step (prefill / speculative verify): one
    dispatch writes W cache slots and scores W positions with
    offset-causal attention (fused_attention qstart) — prompt prefill
    drops from P dispatches to ceil(P/W) MXU-shaped ones.  The cache
    vars live donated in HBM and the step compiles ONCE.  Returns
    (main, cache_startup, feeds, fetches, cache_names); run
    `cache_startup` to (re)zero the caches before each generation.
    Built under unique_name.guard(), so weights are shared by name with
    gpt2_lm_program / gpt2_logits_program built in the same process."""
    import paddle_tpu as fluid

    t_max = t_max or hp.n_ctx
    assert t_max <= hp.n_ctx, (
        "t_max %d exceeds the position table n_ctx %d" % (t_max, hp.n_ctx))
    width = int(width)
    assert 1 <= width <= t_max, (width, t_max)
    dh = hp.d_model // hp.n_head
    main = fluid.Program()
    cache_startup = fluid.Program()  # ONLY cache zeroing lands here
    throwaway_startup = fluid.Program()  # param inits (weights come from
    # the training/logits program's startup via shared names)
    cache_names = []
    with fluid.program_guard(main, throwaway_startup), unique_name.guard():
        # static batch: the caches are [batch, ...] state, so the whole
        # step graph keeps concrete shapes (one compile, no DYN dims)
        ids = layers.data("step_ids", shape=[batch, width], dtype="int64",
                          append_batch_size=False)
        pos = layers.data("pos", shape=[1], dtype="int64",
                          append_batch_size=False)
        pos_vec = None
        if width > 1:
            pos_vec = layers.data("pos_vec", shape=[width], dtype="int64",
                                  append_batch_size=False)
        emb_attr = weight("emb.w")
        tok = layers.embedding(
            ids, size=[hp.vocab_size, hp.d_model], param_attr=emb_attr
        )  # [B, W, D] (W == 1 squeezes in the lookup)
        tok = layers.reshape(tok, shape=[batch, width, hp.d_model])
        if getattr(hp, "use_rotary", False):
            x = tok  # RoPE rotates q/k by position inside cached attention
        else:
            pos_table = layers.create_parameter(
                shape=[hp.n_ctx, hp.d_model], dtype="float32",
                attr=weight("pos_emb.w", 0.01),
            )
            if width == 1:
                pos_row = layers.reshape(layers.gather(pos_table, pos),
                                         shape=[1, 1, hp.d_model])
                x = layers.elementwise_add(tok, pos_row)
            else:
                pos_rows = layers.gather(pos_table, pos_vec)  # [W, D]
                x = layers.elementwise_add(tok, pos_rows, axis=1)
        from .decode_cache import add_cache_zero_fills, create_kv_caches

        blk = main.global_block()
        n_kv = getattr(hp, "n_kv_head", None) or hp.n_head
        kv_caches, cache_names = create_kv_caches(
            blk, "gpt2", hp.n_layer, batch, n_kv, t_max, dh,
            dtype=cache_dtype)
        add_cache_zero_fills(
            cache_startup,
            [(n, (batch, n_kv, t_max, dh)) for n in cache_names],
            dtype=cache_dtype)
        for cache in kv_caches:
            cache["pos"] = pos
            if pos_vec is not None:
                cache["pos_vec"] = pos_vec
            x = _block(x, hp, is_test=True, cache=cache)
        x = layers.layer_norm(x, begin_norm_axis=2)
        logits = _tied_logits(x, hp, emb_attr.name)
        if width == 1:
            logits = layers.reshape(logits, shape=[batch, hp.vocab_size])
        feeds = ["step_ids", "pos"] + (["pos_vec"] if pos_vec is not None
                                       else [])
        # PR 11 closed-gap: the matmul-epilogue fuse bundle now rewrites
        # DECODE programs too (fc bias+act, SwiGLU diamonds, residual-LN
        # pairs -> the fused ops).  Row-independent
        # lowerings keep the serving exactness contract intact; the fetch
        # is protected so no fuse can fold it away.
        _apply_decode_epilogue_passes(main, logits)
    return main, cache_startup, feeds, [logits], cache_names


def gpt2_ragged_step_program(hp=GPT2Config, batch=4, t_max=None, width=8,
                             cache_dtype="float32", cache_prefix="gpt2"):
    """The continuous-batching serving step (serving/engine.py's ONE
    compiled program): width-W decode over a POOL of `batch` slots where
    every slot sits at its own position.

        feeds:  step_ids   [B, W] int64 — per-slot token columns (a
                           prefilling slot carries a prompt chunk, a
                           decoding slot its current token in column 0,
                           a free slot padding)
                pos_rows   [B] int64 — each slot's global write/query
                           base position (qstart)
                width_rows [B] int64 — how many of the W columns are
                           REAL for each slot (1 for decode, chunk len
                           for prefill, 0 for free slots); columns
                           beyond it are never written to the cache
                pos_mat    [B, W] int64 — per-slot absolute positions
                           pos_rows[b] + i (clipped into the position
                           table) for the position embedding / RoPE
        fetch:  logits [B, W, vocab] — row b column i predicts position
                pos_rows[b] + i + 1 for that slot's request
        state:  the SAME per-layer gpt2_{k,v}cache_* persistables as
                gpt2_decode_step_program (shared scope, shared names);
                `cache_prefix` renames them — a DRAFT model's step
                program sharing the target's scope (self-draft
                speculation) must keep its own KV pool

    Cache writes go through slot_cache_write (per-row position + width,
    out-of-width columns dropped) and attention masks per-row offset-
    causal (fused_attention vector qstart), so ONE dispatch interleaves
    prompt prefill for newly admitted requests with single-token decode
    for in-flight ones — occupancy changes only change feed VALUES,
    never shapes: the step compiles exactly once.  Exactness: row b's
    logits are bit-identical to the same request running solo in the
    same program (row-independent math; masked lanes contribute exact
    zeros), which is the serving engine's per-request contract.
    Returns (main, cache_startup, feeds, fetches, cache_names)."""
    import paddle_tpu as fluid

    t_max = t_max or hp.n_ctx
    assert t_max <= hp.n_ctx, (
        "t_max %d exceeds the position table n_ctx %d" % (t_max, hp.n_ctx))
    width = int(width)
    assert 1 <= width <= t_max, (width, t_max)
    dh = hp.d_model // hp.n_head
    main = fluid.Program()
    cache_startup = fluid.Program()
    throwaway_startup = fluid.Program()
    with fluid.program_guard(main, throwaway_startup), unique_name.guard():
        ids = layers.data("step_ids", shape=[batch, width], dtype="int64",
                          append_batch_size=False)
        pos_rows = layers.data("pos_rows", shape=[batch], dtype="int64",
                               append_batch_size=False)
        width_rows = layers.data("width_rows", shape=[batch], dtype="int64",
                                 append_batch_size=False)
        pos_mat = layers.data("pos_mat", shape=[batch, width],
                              dtype="int64", append_batch_size=False)
        emb_attr = weight("emb.w")
        tok = layers.embedding(
            ids, size=[hp.vocab_size, hp.d_model], param_attr=emb_attr
        )
        tok = layers.reshape(tok, shape=[batch, width, hp.d_model])
        if getattr(hp, "use_rotary", False):
            x = tok  # RoPE rotates q/k by pos_mat inside cached attention
        else:
            pos_table = layers.create_parameter(
                shape=[hp.n_ctx, hp.d_model], dtype="float32",
                attr=weight("pos_emb.w", 0.01),
            )
            pos_emb = layers.gather(pos_table, pos_mat)  # [B, W, D]
            x = layers.elementwise_add(tok, pos_emb)
        from .decode_cache import add_cache_zero_fills, create_kv_caches

        blk = main.global_block()
        n_kv = getattr(hp, "n_kv_head", None) or hp.n_head
        kv_caches, cache_names = create_kv_caches(
            blk, cache_prefix, hp.n_layer, batch, n_kv, t_max, dh,
            dtype=cache_dtype)
        add_cache_zero_fills(
            cache_startup,
            [(n, (batch, n_kv, t_max, dh)) for n in cache_names],
            dtype=cache_dtype)
        for cache in kv_caches:
            cache["pos_rows"] = pos_rows
            cache["width_rows"] = width_rows
            if getattr(hp, "use_rotary", False):
                cache["pos_mat"] = pos_mat
            x = _block(x, hp, is_test=True, cache=cache)
        x = layers.layer_norm(x, begin_norm_axis=2)
        logits = _tied_logits(x, hp, emb_attr.name)
        # the continuous-batching step gets the same matmul-epilogue
        # bundle as the classic decode step (PR 11's "training programs
        # only" limit closed); per-row lowerings preserve pooled == solo
        _apply_decode_epilogue_passes(main, logits)
    feeds = ["step_ids", "pos_rows", "width_rows", "pos_mat"]
    return main, cache_startup, feeds, [logits], cache_names


def _apply_decode_epilogue_passes(main, logits):
    """Apply the matmul-epilogue fuse bundle to a decode/serving step
    program, protecting the logits fetch (a fuse deletes every
    intermediate of its chain; the fetch must survive)."""
    from ..transpiler.pass_registry import apply_pass

    prev = tuple(getattr(main, "_protected_fetch_names", ()) or ())
    main._protected_fetch_names = tuple(
        dict.fromkeys(prev + (logits.name,)))
    apply_pass(main, "matmul_epilogue_fuse_pass")


def _prefill_cached(exe, step_main, fetches, ids):
    """Feed the prompt one token at a time (filling the caches); returns
    the logits after the last prompt token (they predict position p)."""
    logits = None
    for t in range(ids.shape[1]):
        (logits,) = exe.run(
            step_main,
            feed={"step_ids": ids[:, t:t + 1],
                  "pos": np.array([t], "int64")},
            fetch_list=fetches,
        )
    return logits


def _speculative_core(
        exe, tgt_step_main, tgt_cache_startup, tgt_step_fetch,
        tgt_wide_main, tgt_wide_fetch, spec_k,
        draft_step_main, draft_cache_startup, draft_step_fetch,
        prompt_ids, max_new_tokens, draft_scope,
        target_pick, draft_pick, resolve_round):
    """Shared speculative round machinery (greedy and sampling variants
    plug in their token rules):

    - target_pick(logits [B, V]) -> [B] token (prefill / capacity-tail)
    - draft_pick(logits [B, V]) -> ([B] token, aux) — aux rides to the
      resolver (the sampling variant records the draft's filtered probs)
    - resolve_round(wl [B, spec_k, V], drafts, aux) ->
      (accepted token list, cur [B], j) — wl row i is the target
      distribution at position pos+i+1 conditioned on chunk[:, :i+1];
      j tokens were accepted, `cur` goes to position pos+j+1

    Round shape: the draft proposes k = spec_k-1 tokens one-step-at-a-
    time, ONE width-spec_k target dispatch scores anchor+drafts, the
    resolver keeps the longest valid prefix.  Rollback is free by
    construction: rejected tokens' K/V sit beyond the accepted position,
    never attended (<=pos masking) and overwritten before first use.
    Near cache capacity the tail falls back to one-token target steps (a
    fixed-width verify write would clamp onto valid slots)."""
    from ..core.scope import global_scope
    from .decode_cache import probe_cache_len, validate_cached_call

    prompt_ids = np.asarray(prompt_ids, "int64")
    b, p = prompt_ids.shape
    spec_k = int(spec_k)
    if spec_k < 2:
        raise ValueError(
            "speculative decoding needs spec_k >= 2 (the wide verify "
            "program needs width > 1; spec_k == 1 is just the plain "
            "cached generator)")
    validate_cached_call(tgt_step_main, "gpt2", "step_ids", b, p,
                         max_new_tokens)
    t_max = probe_cache_len(tgt_wide_main, "gpt2")
    step_t_max = probe_cache_len(tgt_step_main, "gpt2")
    if t_max != step_t_max:
        raise ValueError(
            "speculative decode: wide program cache length %d != step "
            "program's %d — both must address the SAME cache"
            % (t_max, step_t_max))
    from .decode_cache import probe_cache_dtype

    wd = probe_cache_dtype(tgt_wide_main, "gpt2")
    sd = probe_cache_dtype(tgt_step_main, "gpt2")
    if wd != sd:
        raise ValueError(
            "speculative decode: wide program cache dtype %s != step "
            "program's %s — build both with the same cache_dtype"
            % (wd, sd))
    draft_scope = draft_scope if draft_scope is not None else global_scope()

    def run_draft(main, feed, fetches):
        return exe.run(main, feed=feed, fetch_list=fetches,
                       scope=draft_scope)

    # prefill BOTH caches with the prompt; target via its wide program
    exe.run(tgt_cache_startup)
    run_draft(draft_cache_startup, {}, [])
    tgt_logits = prefill_cached_chunked(
        exe, tgt_wide_main, tgt_wide_fetch, prompt_ids, spec_k, t_max)
    for t in range(p):
        run_draft(
            draft_step_main,
            feed={"step_ids": prompt_ids[:, t:t + 1],
                  "pos": np.array([t], "int64")},
            fetches=draft_step_fetch)

    out = [prompt_ids[:, i] for i in range(p)]
    # batch rows advance in lockstep on the SLOWEST row's acceptance —
    # every row's tokens stay valid under its own rule regardless
    cur = target_pick(tgt_logits)  # token @ position p
    pos = p
    proposals = accepted_total = rounds = 0
    while pos < p + max_new_tokens:
        out.append(cur)
        if pos + 1 >= p + max_new_tokens:
            break
        if pos + spec_k > t_max:
            # capacity tail: one-token target steps
            (tl,) = exe.run(
                tgt_step_main,
                feed={"step_ids": cur[:, None],
                      "pos": np.array([pos], "int64")},
                fetch_list=tgt_step_fetch)
            cur = target_pick(tl)
            pos += 1
            continue
        k = min(spec_k - 1, p + max_new_tokens - pos - 2)
        drafts, aux = [], []
        (dl,) = run_draft(
            draft_step_main,
            feed={"step_ids": cur[:, None], "pos": np.array([pos], "int64")},
            fetches=draft_step_fetch)
        for i in range(k):
            tok, a = draft_pick(dl)
            drafts.append(tok)
            aux.append(a)
            (dl,) = run_draft(
                draft_step_main,
                feed={"step_ids": tok[:, None],
                      "pos": np.array([pos + 1 + i], "int64")},
                fetches=draft_step_fetch)
        # ONE target dispatch scores cur + the k draft tokens: row i is
        # the target distribution at position pos+i+1
        chunk = np.stack([cur] + drafts, axis=1)
        if chunk.shape[1] < spec_k:
            chunk = np.pad(chunk, ((0, 0), (0, spec_k - chunk.shape[1])))
        (wl,) = exe.run(
            tgt_wide_main,
            feed={"step_ids": chunk,
                  "pos": np.array([pos], "int64"),
                  "pos_vec": np.minimum(
                      np.arange(pos, pos + spec_k, dtype="int64"),
                      t_max - 1)},
            fetch_list=tgt_wide_fetch)
        rounds += 1
        proposals += k
        acc, cur, j = resolve_round(np.asarray(wl), drafts, aux)
        out.extend(acc)
        accepted_total += j
        pos = pos + 1 + j
    tokens = np.stack(out, axis=1)[:, :p + max_new_tokens]
    stats = {
        "rounds": rounds,
        "proposed": proposals,
        "accepted": accepted_total,
        "accept_rate": (accepted_total / proposals) if proposals else 1.0,
    }
    return tokens, stats


def speculative_generate_cached(
        exe, tgt_step_main, tgt_cache_startup, tgt_step_fetch,
        tgt_wide_main, tgt_wide_fetch, spec_k,
        draft_step_main, draft_cache_startup, draft_step_fetch,
        prompt_ids, max_new_tokens, draft_scope=None):
    """Speculative GREEDY decoding: the resolver keeps the longest
    prefix where every batch row's draft equals the target's argmax,
    then takes the target's bonus/correction token.  Output is EXACTLY
    the target's own greedy_generate_cached sequence for any draft —
    the draft only changes how many target dispatches it takes
    (>= 1 + ceil(new/(k+1)) at full acceptance vs `new`).
    Beyond-reference (the reference era predates speculative decoding);
    the standard TPU serving recipe for dispatch-bound decode.
    draft_scope: the draft model's own fluid.Scope (separate weights +
    caches); defaults to the CURRENT scope (self-draft).  Returns
    (tokens [B, P+new], accept_stats dict)."""

    def target_pick(logits):
        return np.asarray(logits).argmax(-1).astype("int64")

    def draft_pick(logits):
        return np.asarray(logits).argmax(-1).astype("int64"), None

    def resolve(wl, drafts, aux):
        # the shared resolver rule (decode_cache.greedy_accept_len) —
        # the serving engine's in-pool rounds resolve with the same one
        from .decode_cache import greedy_accept_len

        tgt_next = wl.argmax(-1).astype("int64")  # [B, spec_k]
        j = greedy_accept_len(tgt_next, drafts)
        # bonus (all accepted) or correction (first mismatch)
        return list(drafts[:j]), tgt_next[:, j], j

    return _speculative_core(
        exe, tgt_step_main, tgt_cache_startup, tgt_step_fetch,
        tgt_wide_main, tgt_wide_fetch, spec_k,
        draft_step_main, draft_cache_startup, draft_step_fetch,
        prompt_ids, max_new_tokens, draft_scope,
        target_pick, draft_pick, resolve)


def speculative_sample_generate_cached(
        exe, tgt_step_main, tgt_cache_startup, tgt_step_fetch,
        tgt_wide_main, tgt_wide_fetch, spec_k,
        draft_step_main, draft_cache_startup, draft_step_fetch,
        prompt_ids, max_new_tokens, temperature=1.0, top_k=0, top_p=1.0,
        seed=None, draft_scope=None):
    """Speculative SAMPLING (the rejection-sampling scheme): the draft
    proposes d ~ p_d, accepted with prob min(1, p_t(d)/p_d(d)); on
    rejection the token re-samples from normalize(max(p_t - p_d, 0)).
    The output distribution is EXACTLY the target's filtered sampling
    distribution (same temperature/top_k/top_p applied to both models'
    logits) for ANY draft.  A round stops at the first index where ANY
    batch row rejects — earlier acceptances stand (valid draws
    regardless of other rows); at the stop index accepted rows keep
    their draft token and rejected rows draw the residual.  Returns
    (tokens [B, P+new], accept_stats dict)."""
    from .decode_cache import filtered_probs, residual_probs, sample_rows

    rng = np.random.RandomState(seed)
    b = np.asarray(prompt_ids).shape[0]

    def probs(logits):
        return filtered_probs(logits, temperature, top_k, top_p)

    def target_pick(logits):
        return sample_rows(probs(logits), rng)

    def draft_pick(logits):
        pd = probs(logits)
        return sample_rows(pd, rng), pd

    def resolve(wl, drafts, aux):
        j, acc = 0, []
        while j < len(drafts):
            pt = probs(wl[:, j])
            pd = aux[j]
            d = drafts[j]
            ratio = (np.take_along_axis(pt, d[:, None], 1).reshape(-1)
                     / np.maximum(
                         np.take_along_axis(pd, d[:, None], 1).reshape(-1),
                         1e-12))
            reject = rng.rand(b) > ratio
            if not reject.any():
                acc.append(d)
                j += 1
                continue
            # stop: rejected rows draw the shared residual rule
            # (decode_cache.residual_probs — the serving engine's keyed
            # resolver computes the same distribution); accepted rows
            # keep d (a valid draw regardless of others)
            repl = sample_rows(residual_probs(pt, pd), rng)
            return acc, np.where(reject, repl, d).astype("int64"), j
        # every draft accepted: bonus from the target's last row
        return acc, sample_rows(probs(wl[:, len(drafts)]), rng), j

    return _speculative_core(
        exe, tgt_step_main, tgt_cache_startup, tgt_step_fetch,
        tgt_wide_main, tgt_wide_fetch, spec_k,
        draft_step_main, draft_cache_startup, draft_step_fetch,
        prompt_ids, max_new_tokens, draft_scope,
        target_pick, draft_pick, resolve)


def _dispatch_prefill(exe, step_main, fetches, ids, prefill):
    """Prefill the caches with `ids`: chunked through the wide program
    when `prefill` = (wide_main, wide_fetches, width[, t_max]) is given,
    one-token steps otherwise.  The wide program's cache length and
    static batch are VALIDATED here — a wrong t_max would let the
    chunked writes clamp onto valid slots, and a beam path needs the
    wide program built with batch = B * beam_size."""
    if prefill is None:
        return _prefill_cached(exe, step_main, fetches, ids)
    from .decode_cache import probe_cache_len

    from .decode_cache import probe_cache_dtype

    wm, wf, width = prefill[0], prefill[1], int(prefill[2])
    t_max = probe_cache_len(wm, "gpt2")
    step_t_max = probe_cache_len(step_main, "gpt2")
    if t_max != step_t_max:
        raise ValueError(
            "prefill wide program cache length %d != the step program's "
            "%d — both must address the SAME cache capacity or the "
            "chunked writes land on wrong slots" % (t_max, step_t_max))
    wd, sd = probe_cache_dtype(wm, "gpt2"), probe_cache_dtype(step_main,
                                                             "gpt2")
    if wd != sd:
        raise ValueError(
            "prefill wide program cache dtype %s != the step program's "
            "%s — build both with the same cache_dtype" % (wd, sd))
    if len(prefill) > 3 and int(prefill[3]) != t_max:
        raise ValueError(
            "prefill t_max %d does not match the wide program's cache "
            "length %d" % (int(prefill[3]), t_max))
    ids_var = wm.global_block().var("step_ids")
    wb, ww = int(ids_var.shape[0]), int(ids_var.shape[1])
    if ww != width:
        raise ValueError(
            "prefill width %d != the wide program's step_ids width %d"
            % (width, ww))
    if wb != ids.shape[0]:
        raise ValueError(
            "prefill wide program batch %d != %d rows to prefill (beam "
            "paths need the wide program built with batch = B * "
            "beam_size)" % (wb, ids.shape[0]))
    return prefill_cached_chunked(exe, wm, wf, ids, width, t_max)


def prefill_cached_chunked(exe, wide_main, wide_fetches, ids, width,
                           t_max):
    """Fill the caches with the prompt in ceil(P/W) width-W dispatches
    (gpt2_decode_step_program(width=W)) instead of P one-token steps;
    returns the logits predicting position P (identical to one-token
    prefill).  The last chunk re-anchors to t_max - W when it would
    write past the cache (rewriting earlier slots with the same tokens
    is idempotent); pad rows beyond the prompt land in slots the
    generation loop overwrites before ever attending them."""
    from .decode_cache import run_chunked_ids

    ids = np.asarray(ids, "int64")
    _b, p = ids.shape
    logits = last_c0 = None
    for c0, lg in run_chunked_ids(exe, wide_main, wide_fetches, ids,
                                  width, t_max, "step_ids",
                                  has_pos_vec=True):
        logits, last_c0 = lg, c0
    return logits[:, (p - 1) - last_c0]


def greedy_generate_cached(exe, step_main, cache_startup, fetches,
                           prompt_ids, max_new_tokens, prefill=None):
    """Greedy decoding through the KV-cached step program: prefill fills
    the caches from the prompt, then each new token costs one
    O(T_max * d) step.  Matches greedy_generate token-for-token.
    prefill: optional (wide_main, wide_fetches, width, t_max) from
    gpt2_decode_step_program(width=W) — chunked prefill in ceil(P/W)
    dispatches instead of P."""
    from .decode_cache import validate_cached_call

    prompt_ids = np.asarray(prompt_ids, "int64")
    b, p = prompt_ids.shape
    validate_cached_call(step_main, "gpt2", "step_ids", b, p,
                         max_new_tokens)
    exe.run(cache_startup)  # (re)zero the caches for this generation
    out = [prompt_ids[:, i] for i in range(p)]
    logits = _dispatch_prefill(exe, step_main, fetches, prompt_ids,
                               prefill)
    for t in range(p, p + max_new_tokens):
        nxt = np.asarray(logits).argmax(axis=-1).astype("int64")
        out.append(nxt)
        if t + 1 >= p + max_new_tokens:
            break
        (logits,) = exe.run(
            step_main,
            feed={"step_ids": nxt[:, None], "pos": np.array([t], "int64")},
            fetch_list=fetches,
        )
    return np.stack(out, axis=1)


def _prompt_buffer(main, prompt_ids, max_new_tokens, pad_id):
    """Shared decode prologue: validate the prompt against the program's
    width and left-align it in a pad-filled [B, T] buffer."""
    T = int(main.global_block().vars["ids"].shape[1])
    prompt_ids = np.asarray(prompt_ids, "int64")
    b, p = prompt_ids.shape
    assert p >= 1, "empty prompt: seed generation with at least a BOS token"
    assert p + max_new_tokens <= T, (
        "program seq_len %d < prompt %d + new %d" % (T, p, max_new_tokens)
    )
    buf = np.full((b, T), pad_id, "int64")
    buf[:, :p] = prompt_ids
    return buf, p


def greedy_generate(exe, main, fetches, prompt_ids, max_new_tokens,
                    pad_id=0):
    """Greedy decoding on a fixed-shape logits program: the prompt is
    right-padded to the program's T, each step feeds the updated ids and
    reads the logits at the last real position.  One XLA compile total
    (static shapes); causal masking makes the padded tail invisible.

    prompt_ids: [B, P] int64.  Returns [B, P + max_new_tokens] int64.
    """
    buf, p = _prompt_buffer(main, prompt_ids, max_new_tokens, pad_id)
    cur = p
    for _ in range(max_new_tokens):
        (logits,) = exe.run(main, feed={"ids": buf}, fetch_list=fetches)
        nxt = np.asarray(logits)[:, cur - 1, :].argmax(axis=-1)
        buf[:, cur] = nxt
        cur += 1
    return buf[:, :cur]


def beam_generate(exe, main, fetches, prompt_ids, max_new_tokens,
                  beam_size=4, eos_id=None, pad_id=0, length_penalty=0.0):
    """Beam-search decoding on the same fixed-shape logits program as
    greedy_generate.  Returns (ids [B, T_out], scores [B])."""
    from ..contrib.decoder.beam_search_decoder import full_sequence_beam_search

    buf, p = _prompt_buffer(main, prompt_ids, max_new_tokens, pad_id)

    def logits_fn(rows, cur):
        (logits,) = exe.run(main, feed={"ids": rows}, fetch_list=fetches)
        return np.asarray(logits)[:, cur - 1, :]

    return full_sequence_beam_search(
        logits_fn, buf, p, beam_size, p + max_new_tokens,
        eos_id if eos_id is not None else -1, pad_id, length_penalty,
    )


def beam_generate_cached(exe, step_main, cache_startup, fetches, prompt_ids,
                         max_new_tokens, beam_size=4, eos_id=None, pad_id=0,
                         length_penalty=0.0, prefill=None):
    """Beam-search decoding through the KV-cached step program: the step
    program must be built with batch = B * beam_size; surviving beams'
    caches shuffle via a gather/assign reorder program each step (the
    reference's beam-search cache plumbing).  prefill: optional
    (wide_main, wide_fetches, width, t_max) chunked prompt prefill —
    the wide program must ALSO be built with batch = B * beam_size.
    Returns (ids [B, T_out], scores [B])."""
    from ..contrib.decoder.beam_search_decoder import incremental_beam_search
    from .decode_cache import (
        make_cache_reorder_program,
        validate_cached_call,
    )

    prompt_ids = np.asarray(prompt_ids, "int64")
    b, p = prompt_ids.shape
    validate_cached_call(step_main, "gpt2", "step_ids", b, p,
                         max_new_tokens, beams=beam_size)
    sb = step_main.global_block()
    r = b * beam_size
    cache_shapes = [
        (n, v.shape, v.dtype) for n, v in sb.vars.items()
        if n.startswith(("gpt2_kcache_", "gpt2_vcache_"))
    ]
    reorder = make_cache_reorder_program(cache_shapes, r)

    exe.run(cache_startup)
    rep = np.repeat(prompt_ids, beam_size, axis=0)
    logits = _dispatch_prefill(exe, step_main, fetches, rep, prefill)

    def step_fn(tokens, pos):
        (lg,) = exe.run(step_main,
                        feed={"step_ids": tokens,
                              "pos": np.array([pos], "int64")},
                        fetch_list=fetches)
        return lg

    def reorder_fn(rows):
        exe.run(reorder, feed={"parents": rows.astype("int64")},
                fetch_list=[])

    return incremental_beam_search(
        step_fn, reorder_fn, logits, prompt_ids, p, beam_size,
        p + max_new_tokens, eos_id if eos_id is not None else -1, pad_id,
        length_penalty)


def sample_generate_cached(exe, step_main, cache_startup, fetches,
                           prompt_ids, max_new_tokens, temperature=1.0,
                           top_k=0, top_p=1.0, seed=None, eos_id=None,
                           pad_id=0, prefill=None):
    """Stochastic decoding through the KV-cached step: temperature
    scaling, top-k and/or nucleus (top-p) filtering, seeded numpy
    sampling.  top_k=1 reduces to greedy.  prefill: optional
    (wide_main, wide_fetches, width, t_max) — chunked prompt prefill in
    ceil(P/W) dispatches.  Returns [B, P + new] int64."""
    from .decode_cache import sample_from_logits, validate_cached_call

    prompt_ids = np.asarray(prompt_ids, "int64")
    b, p = prompt_ids.shape
    validate_cached_call(step_main, "gpt2", "step_ids", b, p,
                         max_new_tokens)
    rng = np.random.RandomState(seed)
    exe.run(cache_startup)
    logits = _dispatch_prefill(exe, step_main, fetches, prompt_ids,
                               prefill)
    out = [prompt_ids[:, i] for i in range(p)]
    done = np.zeros(b, bool)
    for t in range(p, p + max_new_tokens):
        nxt = sample_from_logits(logits, rng, temperature, top_k, top_p)
        if eos_id is not None:
            nxt = np.where(done, pad_id, nxt)
            done |= nxt == eos_id
        out.append(nxt)
        if t + 1 >= p + max_new_tokens or (eos_id is not None and done.all()):
            break
        (logits,) = exe.run(step_main, feed={
            "step_ids": nxt[:, None], "pos": np.array([t], "int64")},
            fetch_list=fetches)
    # early all-eos exit: pad to the documented [B, P + new] width
    while len(out) < p + max_new_tokens:
        out.append(np.full(b, pad_id, "int64"))
    return np.stack(out, axis=1)
