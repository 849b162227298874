"""The lowered train step of GPT-2, OLMoE and LFM2, and a long-sequence
attention core, as they were before the flash kernel learnt a V of another
width than Q and K, `moe_ffn` a scaling factor and an epsilon, and
`rotary_embed` a pairing (PR 37): each program is lowered for the TPU on
this host (the kernels engage: T = 512, heads of 64 and 128), and its
StableHLO text is digested with every Mosaic payload replaced by the
digest of its module printed WITHOUT debug locations (a payload carries
the kernels' source line numbers, which an edit anywhere above them in
pallas_kernels.py moves).  The digests below were taken from commit
ec9cdf7, the parent of PR 37, by this file's own `_digest`: the text an
accepted cell's step lowers to did not change by a byte, and the kernels'
modules did not change by an instruction.

A later PR that changes one of these lowerings on purpose re-takes the
digests from its own tree and says so.

PR 39 did, for `lfm2` alone: where `moe_ffn` holds a share of its experts
its row work runs over the live chunks (ops/moe_ops.py), so the LFM2 step's
text changed on purpose and its digest below is taken from PR 39's tree by
this file's `_digest`.  Its Mosaic-call count stayed 9, and `gpt2`, `olmoe`
and `two_kernel_backward` stayed what they were at ec9cdf7: no kernel
instance was added, and a step whose op holds every expert did not move.

PR 40 added `trinity` (a head width of its own, a window on three of four
attention layers, an output gate, `expert_bias_update` with a rate and a
bound), its digest taken from PR 40's tree by this file's `_digest`; the
others stayed what they were: `multi_head_attention`'s new arguments at
their defaults, and an `expert_bias_update` without attributes, lower to
the text they lowered to.

PR 41 gave the windowed flash kernels the band as their grid.  All five
digests and Mosaic counts above stayed what they were, `trinity`'s too: its
tiny program runs T = 512 in one block, where the band is the grid.  Three
attention cores alone at Trinity-Mini's length (T = 8192, heads of 128,
blocks of 1024) were added: `full_causal_8192` (no window) and
`window_covers_8192` (window 8192), both taken from PR 41's PARENT commit
6e8e801 by this file's `_digest` (without a window, or under one that covers
the sequence, the kernels are the parent's to the instruction), and
`window_2048_of_8192`, taken from PR 41's tree, which is NOT what the parent
lowered (538099ce...: the full grid): its grid is the band's 3 blocks.

PR 43 undid `rotary_embed`'s published (2i, 2i+1) pairing with a constant
permutation matmul in place of a strided index (ops/nn_ops._deinterleave),
under the op's `interleaved` attribute alone.  The other eight digests and
Mosaic counts did not move: no pinned program sets the attribute, and a
rotate-half `rotary_embed` lowers to the text it lowered to.  `kanana2` (a
tiny kanana-2: latent attention at the flash kernel's (192, 128), the
attribute set, a share of the experts held) was added, its digest taken
from PR 43's tree by this file's `_digest`; it is NOT what the parent
lowered (5974c9c2...: two gathers forward and two scatters backward a
`rotary_embed`).

PR 44 took the lowering flag and the six kernels behind it away: `fc`,
`fused_swiglu`, `fused_residual_ln`, `layer_norm` and
`softmax_with_cross_entropy` are their dense bodies.  The nine digests above
did not move.  Three programs whose ops that PR edited were added, their
digests taken from its PARENT commit 6a7549d by this file's `_digest`:
`transformer` (Transformer-base's shape at tiny widths: `fc` with bias and
activation, `fused_residual_ln` (every layer norm of the post-LN stack is
fused into one; `gpt2` above holds the one free-standing `layer_norm`);
dense attention at 64, no Mosaic call), `resnet` (ResNet-50 on 32 x 32
images; its loss is `softmax` + `cross_entropy`, so it holds none of the
edited ops and is the control) and `ouro` (`fused_swiglu`): the one lowering
left is the one the ledger measured.

PR 45 added `kimi_linear` (a tiny Kimi-Linear: two KDA layers around the
chunkwise `kda_attention` op, three `causal_conv` ops a layer, and
`latent_attention(rotary=False)` at the flash kernel's (192, 128); a share
of the experts held), its digest taken from PR 45's tree by this file's
`_digest`.  The twelve digests above did not move, `kanana2`'s above all:
`latent_attention`'s new switch builds the ops it built, in their order,
where rotary stays on.

PR 46 made `kda_attention`'s chunk inside two Pallas kernels
(ops/kda_kernels.py: `intra`, and `intra_bwd`, the inside transposed by
hand), so `kimi_linear`'s step changed on purpose: its digest below is
taken from PR 46's final tree (the backward stacks the entering states
float32, as it carries them) by this file's `_digest`, and its Mosaic
calls went from 9 to 15 (three a KDA layer: `intra` in the forward, `intra` again and
`intra_bwd` in the grad op; the tiny program's two KDA layers engage both
kernels at heads of 128, one chunk-block of 8 a head).  The other twelve
digests and counts did not move: no other program holds the op.

PR 47 moved `fc`'s bias and activation in front of its reshape under gelu
and swish (ops/nn_ops.FC_PRODUCT_EPILOGUE_ACTS: the epilogue on the [M, N]
product), so `gpt2`'s step changed on purpose (one gelu `fc` a layer): its
digest below is taken from PR 47's tree by this file's `_digest`; its
Mosaic calls stayed 3.  The other twelve digests and counts did not move:
`transformer`'s `fc` ops carry relu or no activation, `resnet`'s none, and
no other pinned program holds an `fc` under gelu or swish.

PR 48 added `qwen3_next` (a tiny Qwen3-Next: three Gated DeltaNet layers
around the chunkwise `gated_delta_attention` op, one key head read by two
value heads of 128, ONE `causal_conv` a layer; a gated attention layer at
the flash kernel's new (256, 256) with rotary on 64 of its lanes and the
1 + w gains; a share of softmax-routed experts held), its digest taken from
PR 48's tree by this file's `_digest`: 18 Mosaic calls (three a GDN layer:
`gdn_intra` in the forward, again and `gdn_intra_bwd` in the grad op; the
nine every share-holding program with one flash core has).  The thirteen digests and counts above did not move,
`kimi_linear`'s above all: the carry its op shares with the new one
(`kda_ops._carry_forward` / `_carry_backward`) traces to the text it traced
to, and `multi_head_attention`'s `rotary_dim` and `norm_unit_offset` at
their defaults build `trinity`'s and `lfm2`'s layers op for op.

PR 50 made that carry Pallas kernels (ops/kda_kernels.py: `carry`, and
`carry_bwd`; the state in a VMEM scratch across a head's chunks, where
three `lax.scan`s and three hoisted products ran), so `kimi_linear`'s and
`qwen3_next`'s steps changed on purpose: their digests below are taken from
PR 50's tree by this file's `_digest`, and their Mosaic calls went from 15
to 21 and from 18 to 27 (three more a delta-rule layer: `carry` in the
forward, `carry` keeping the entering states and `carry_bwd` in the grad
op).  The other twelve digests and counts did not move: no other program
holds either op.

PR 53 made the causal flash kernels of the training path compute a tile by
where it lies (`pallas_kernels._tile_plan`: a tile wholly visible without a
mask, a tile the diagonal or the band's edge cuts in strips over its visible
part), so every causal payload changed on purpose: the digests of the eight
programs that hold a causal flash core (`gpt2`, `olmoe`, `lfm2`, `trinity`,
`kanana2`, `kimi_linear`, `qwen3_next`, `ouro`) and of the four causal cores
(`two_kernel_backward`: its forward alone, the dq and dk/dv kernels are what
they were) are re-taken from PR 53's tree by this file's `_digest`.  No
Mosaic count moved (a kernel's body grew, no kernel was added), and
`transformer` and `resnet` (no flash kernel) did not move.  Three cores the
change must NOT reach were added, their digests taken from PR 53's PARENT
commit ba67ef1 by this file's `_digest`: `non_causal_2048` (a mask-free
`flash_attention`: forward and one-kernel backward), `piece_at_an_offset`
(`flash_attention_piece` under a window at a traced q offset: forward, dq,
dk/dv) and `piece_diagonal_chunk` (the ring's causal chunk, no offset): a
call that cannot know a tile's class when traced, and the ring's entry,
lower to what they lowered to, to the instruction.

PR 56 made the index maps of a causal kernel on the FULL grid name, at a
step the mask skips, the block the head's next live step reads
(`pallas_kernels._band_inner`: a block named twice in a row is not copied in
again), wherever the kernel knows the steps when it is traced (causal, no
traced q offset) and its inner axis has more than one step.  So the four
cores whose causal kernels walk several blocks changed on purpose, in their
index maps alone, and their digests are re-taken from PR 56's tree by this
file's `_digest`: `full_causal_8192`, `window_covers_8192` (T = 8192),
`two_kernel_backward` (T = 16384: forward, dq and dk/dv kernels) and
`piece_diagonal_chunk` (T = 2048 in blocks of 128: the ring's causal chunk
carries no offset, so it is such a call; its one masked body is what it
was).  The other thirteen digests and every Mosaic count did NOT move, and
must not: `window_2048_of_8192` walks the band's grid, whose maps are PR
41's; `non_causal_2048` has no mask and `piece_at_an_offset` a traced
offset; `transformer` and `resnet` hold no flash kernel; and the eight tiny
programs with a causal flash core (`gpt2`, `olmoe`, `lfm2`, `trinity`,
`kanana2`, `kimi_linear`, `qwen3_next`, `ouro`) run T = 512 in ONE block,
where the map stays the grid's own step, as the two GPT-2 cells do at
T = 1024.

PR 57 added `nemotron_h` (a tiny Nemotron-3-Nano: the nine-layer pattern
MEMEM*EME, four Mamba-2 mixers around the chunkwise `mamba2_scan` op at
eight heads of 64 over two groups at state 128, their `causal_conv` with a
bias, a rotary-free attention layer at heads of 128, a share of `relu2`
experts held beside a relu2 shared one, one `expert_bias_update` an expert
layer), its digest taken from PR 57's tree by this file's `_digest`: 18
Mosaic calls (three a Mamba-2 layer, twelve: the chunk scan in the forward,
the walk that keeps the entering states and the reverse walk in the grad
op; the three of a flash core; and three grouped-matmul kernels, which the
share's module-level jitted functions hold once for the four expert layers
that call them).
The seventeen digests and counts above did NOT move: `moe_ffn` without
`expert_act` lowers through the SwiGLU body it lowered through (the op's
jitted loops keep their names and take the body as one more static
argument), `causal_conv` without a `Bias` to the text it lowered to, and
`rms_norm` with a gain of one axis likewise.

PR 58 let the AMP pass carry bfloat16 through the ops that only move values
(contrib/mixed_precision._MOVE_OPS: `split`, `concat`, `expand` run in the
dtype their data arrives in; `expand` sums its gradient's copies in float32
and rounds once whatever its dtype, ops/tensor_ops._tile_copies), so the six
programs whose built Program holds such an op behind a bfloat16 cast-back
changed on purpose, in dtypes and `convert`s alone, and their digests are
re-taken from PR 58's tree by this file's `_digest`: `nemotron_h` (both
splits of every Mamba-2 mixer, the grouped keys' and values' `expand`),
`qwen3_next` (the split after every GDN convolution, the splits of the
partly rotated query and key, the grouped values' `expand`), `kanana2`
(latent attention's three splits a layer; its `concat`s keep a float32
`rotary_embed` input and stay), `kimi_linear` (its latent layer's two
splits, the rotary-free key's `expand` and `concat`), `trinity` and `lfm2`
(the `expand` of the grouped keys and values that no `rotary_embed` stands
before).  No Mosaic count moved.  The other twelve digests did
NOT move, and that is the proof that the steps of the cells they stand
for are the parent's: in `gpt2`, `olmoe`, `ouro`, `transformer` and
`resnet` no `split`, `concat` or `expand` reads the cast-back of a
bfloat16 value, and a float32 `expand` (`kanana2`'s rotated key among
them) lowers to the text `jnp.tile` and its gradient lowered to.

PR 62 gave the short sequences a kernel of their own
(`pallas_kernels.short_attention`, engaged under the blockwise kernel's
lengths: `nn_ops._short_engages`), so `transformer` (4 x 64 + 64, two heads
of 64) moved ON PURPOSE, its digest re-taken from PR 62's tree by this
file's `_digest`: 0 Mosaic payloads became 6 (the forward op's body, the
grad op's re-traced forward and the backward, for each of two
configurations under the key bias: causal, the decoder's self-attention,
and not, the encoder's and the cross-attention).  The other seventeen did
NOT move: every
attention they hold has T >= 1024 and takes the blockwise kernel through
the branch it took, and that is the proof of it.

PR 63 folded the heads' transposes around each `fused_attention` of the
Transformer builder's Program into the op (`attention_layout_fuse_pass`,
layout "bthd") and gave the one-tile kernel a form that reads the
projections' [B, T, H d] in place, so `transformer` moved ON PURPOSE again,
its digest re-taken from PR 63's tree by this file's `_digest`: the same 6
Mosaic payloads (now `_inplace_fwd_call` / `_inplace_bwd_call`'s, none of
`_short_*_call`'s left beside them) and not one [0, 2, 1, 3] transpose in
the text, where the step held 48.  The other seventeen did NOT move: no
other builder applies the pass, a `fused_attention` op without the
attribute lowers through the branch it took, and `layers.fused_attention`
writes no `layout` attribute at its default.

PR 66 made the block the training path hands the flash kernels a function
of the op's (T, window) (`nn_ops._flash_block(t, window)`: under a window
narrower than T the largest block that divides the window too, not under
512).  All eighteen digests and counts above stayed what they were, and
that is the proof that the fourteen cells without Laguna-XS.2's window did
not move: `trinity`'s tiny program runs a 256 window over T = 512, under
the floor, in the one 512-block it ran in, as Trinity-Mini's cell keeps
1024-blocks under its 2048 window (`window_2048_of_8192`).  `laguna` (a
tiny Laguna-XS.2 at T = 1024: a full layer of one query head and a window
layer of two over one KV head of 128, the gate a head, YaRN on half of the
full layer's head, a 512 window, a share of the experts held) was added,
its digest taken from PR 66's tree by this file's `_digest`: its window
core runs two 512-blocks a side with the diagonal's and the edge's tiles
in strips, and it is NOT what the parent's rule lowers (the window core in
one 1024-block, one tile cut by both: `_flash_block` patched to drop the
window, in a scratch script, gives another digest on this tree)."""

import base64
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu.core.trace import build_traced_function
from paddle_tpu.models import (gpt2, kanana2, kimi_linear, laguna, lfm2,
                               nemotron_h, olmoe, ouro, qwen3_next, resnet,
                               transformer, trinity)
from paddle_tpu.ops import pallas_kernels as pk

SEQ = 512
BODY = re.compile(r'\\22body\\22: \\22([^\\]*)\\22')


class G(gpt2.GPT2Config):
    vocab_size, n_ctx, d_model, n_layer, n_head = 512, 512, 128, 2, 2


class O(olmoe.OLMoEConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    num_hidden_layers, num_attention_heads, num_key_value_heads = 2, 1, 1
    num_experts, num_experts_per_tok = 8, 2


class L(lfm2.LFM2MoEConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    moe_intermediate_size, num_hidden_layers, num_dense_layers = 128, 3, 1
    layer_types = ["conv", "full_attention", "conv"]
    num_attention_heads, num_key_value_heads = 2, 1
    num_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class T(trinity.TrinityConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    moe_intermediate_size, num_hidden_layers, num_dense_layers = 128, 4, 1
    layer_types = ["sliding_attention"] * 3 + ["full_attention"]
    num_attention_heads, num_key_value_heads, head_dim = 2, 1, 128
    sliding_window = 256
    num_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class K(kanana2.Kanana2Config):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    moe_intermediate_size, num_hidden_layers, kv_lora_rank = 128, 2, 64
    num_attention_heads = num_key_value_heads = 2
    n_routed_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class M(kimi_linear.KimiLinearConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    moe_intermediate_size, num_hidden_layers, kv_lora_rank = 128, 3, 64
    linear_attn_config = {"kda_layers": [1, 2], "full_attn_layers": [3],
                          "num_heads": 2, "head_dim": 128,
                          "short_conv_kernel_size": 4}
    num_attention_heads = num_key_value_heads = 2
    num_experts, num_experts_per_token = 8, 2
    num_local_experts, expert_offset = 2, 2


class Q(qwen3_next.Qwen3NextConfig):
    vocab_size, hidden_size, num_hidden_layers = 512, 128, 4
    linear_num_key_heads, linear_num_value_heads = 1, 2
    num_attention_heads, num_key_value_heads = 2, 1
    moe_intermediate_size = shared_expert_intermediate_size = 128
    num_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class N(nemotron_h.NemotronHConfig):
    vocab_size, hidden_size, num_hidden_layers = 512, 128, 9
    hybrid_override_pattern = "MEMEM*EME"
    mamba_num_heads, mamba_head_dim, n_groups, ssm_state_size = 8, 64, 2, 128
    num_attention_heads, num_key_value_heads, head_dim = 2, 1, 128
    moe_intermediate_size, moe_shared_expert_intermediate_size = 128, 256
    n_routed_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class A(laguna.LagunaConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 128
    moe_intermediate_size = shared_expert_intermediate_size = 128
    num_hidden_layers, num_key_value_heads = 2, 1
    layer_types = ["full_attention", "sliding_attention"]
    mlp_layer_types = ["dense", "sparse"]
    num_attention_heads_per_layer = [1, 2]
    sliding_window = 512
    num_experts, num_experts_per_tok = 8, 2
    num_local_experts, expert_offset = 2, 2


class U(ouro.OuroConfig):
    vocab_size, hidden_size, intermediate_size = 512, 128, 256
    num_hidden_layers, num_attention_heads, num_key_value_heads = 2, 2, 2
    head_dim = 64


class W(transformer.ModelHyperParams):
    src_vocab_size = trg_vocab_size = 512
    max_length, d_model, d_inner_hid, n_head, n_layer = 64, 128, 256, 2, 2
    fused_attn = True


def _trinity_program(hp, **kw):
    return trinity.trinity_lm_program(hp, bias_rate=0.03, bias_max_step=0.03,
                                      **kw)


def _kimi_program(hp, **kw):
    return kimi_linear.kimi_linear_lm_program(
        hp, bias_rate=0.03, bias_max_step=0.03, **kw)


def _shapes(batch):
    """A host batch as the executor would upload it (jnp.asarray's dtypes)."""
    return {n: jax.ShapeDtypeStruct(a.shape, jnp.asarray(a[:0]).dtype)
            for n, a in batch.items()}


def _lm(build, hp, seq=SEQ):
    """(main, startup, loss name, feeds) of a causal LM builder's train
    program at 2 x seq in bfloat16."""
    main, startup, _, fetches = build(hp, seq_len=seq, lr=1e-3,
                                      use_bf16=True)
    return main, startup, fetches[0].name, {
        "ids": jax.ShapeDtypeStruct((2, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((2, seq), jnp.int32),
        "loss_weight": jax.ShapeDtypeStruct((2, seq), jnp.float32)}


def _transformer():
    """Transformer-base's program shape at tiny widths, 4 x 64 + 64 (the
    one-tile attention kernel, as the cells run it at 64 and 256 since
    PR 62, in place on the projections' [B, T, H d] since PR 63): `fc` with
    bias and activation, `fused_residual_ln`."""
    main, startup, _, fetches = transformer.wmt_transformer_program(
        W, src_len=64, trg_len=64, use_bf16=True)
    return main, startup, fetches[0].name, _shapes(
        transformer.make_fake_batch(4, 64, 64, W))


def _resnet():
    """ResNet-50 on 8 x 3 x 32 x 32 over 10 classes, bfloat16: the
    `resnet50_train` cell's rehearsal."""
    main, startup, _, fetches = resnet.build_resnet_train_program(
        image_shape=(3, 32, 32), class_dim=10, depth=50, lr=0.01,
        use_bf16=True)
    return main, startup, fetches[0].name, {
        "image": jax.ShapeDtypeStruct((8, 3, 32, 32), jnp.float32),
        "label": jax.ShapeDtypeStruct((8, 1), jnp.int32)}


PROGRAMS = {"gpt2": lambda: _lm(gpt2.gpt2_lm_program, G),
            "olmoe": lambda: _lm(olmoe.olmoe_lm_program, O),
            "lfm2": lambda: _lm(lfm2.lfm2_lm_program, L),
            "trinity": lambda: _lm(_trinity_program, T),
            "kanana2": lambda: _lm(kanana2.kanana2_lm_program, K),
            "kimi_linear": lambda: _lm(_kimi_program, M),
            "qwen3_next": lambda: _lm(qwen3_next.qwen3_next_lm_program, Q),
            "ouro": lambda: _lm(ouro.ouro_lm_program, U),
            "nemotron_h": lambda: _lm(nemotron_h.nemotron_h_lm_program, N),
            "laguna": lambda: _lm(laguna.laguna_lm_program, A, 2 * SEQ),
            "transformer": _transformer,
            "resnet": _resnet}

# name -> (sha1 of the normalised text, Mosaic calls in it) at ec9cdf7
# (`lfm2`: at PR 39; `trinity`: at PR 40; `kanana2`: at PR 43; `transformer`,
# `resnet`, `ouro`: at 6a7549d, PR 44's parent; `kimi_linear`: at PR 46;
# `gpt2`: at PR 47; `qwen3_next`: added at PR 48; both delta-rule programs:
# at PR 50; every program and core with a causal flash kernel: at PR 53; the
# three UNTOUCHED cores: at ba67ef1, PR 53's parent; the four cores whose
# causal kernels walk several blocks of the full grid, `piece_diagonal_chunk`
# among them: at PR 56; `nemotron_h`: added at PR 57; the six programs whose
# AMP rewrite flips a `split`, `concat` or `expand`: at PR 58; `transformer`
# again: at PR 62, and at PR 63; `laguna`: added at PR 66)
BEFORE = {
    "nemotron_h": ("424a80a8933a0ec7ee89c4ece3eeca9006e18e92", 18),
    "qwen3_next": ("3d4b8d2d56c5035594075b0508a3614e286234ce", 27),
    "kimi_linear": ("3e0377967224298932fcd5be83fe7ce7f59a2b5b", 21),
    "transformer": ("de649f8715dba25ff5b558116416dbc9d80910c5", 6),
    "resnet": ("84575b13d140437bb64cb8461436105337774a6d", 0),
    "ouro": ("0057fcbecadc1719a1de27cb3b94bb41ac569650", 3),
    "kanana2": ("58a5bd2363cca5fb23fea690fbcbdda362a0ee97", 9),
    "trinity": ("1376e7973e2fe1f118d8b4c0310391977cccd060", 12),
    "laguna": ("436e9f3354da5934a15bf1b697a53f5570de648d", 12),
    "gpt2": ("bfdcbc6f62d3aaf4418dc9bf22e0aaf9dc8a8a4a", 3),
    "olmoe": ("8fc96fbebcde6d156165e9b26443399f6b36494f", 9),
    "lfm2": ("3def3cb90e0a1051d6b1d7f46e50389e659a824d", 9),
    "two_kernel_backward": ("11298a99a31b05beda8f898f4d78ff6e1a7258d4", 3),
    "full_causal_8192": ("5b5142af7fe40d858d5b144ad6aa1e2ff0328207", 2),
    "window_covers_8192": ("06a0539acce2ff8f9fe3049ed5f52419b7a27c44", 2),
    "window_2048_of_8192": ("55fb7bfd281ba57dbb1e66197fc71be9aa664089", 2),
    "non_causal_2048": ("9b7ca3bb75c85e16b56930b0829a721b43a0acac", 2),
    "piece_at_an_offset": ("1d162f089780ed64698a5fda1537085bfc2cb3c6", 3),
    "piece_diagonal_chunk": ("ad6d3cada983238a935695eaf4e5082e997c1f53", 3),
}
# name -> (T, window) of an attention core alone, forward + backward
CORES = {"two_kernel_backward": (16384, 0), "full_causal_8192": (8192, 0),
         "window_covers_8192": (8192, 8192),
         "window_2048_of_8192": (8192, 2048)}


def _untouched(q, k, v, qoff, name):
    """The calls PR 53's tile classes must not reach, at T = 2048, heads of
    128: a scalar to differentiate.  (PR 56's index maps reach the third,
    the ring's chunk without an offset, and neither of the others.)"""
    if name == "non_causal_2048":
        return jnp.sum(pk.flash_attention(
            q, k, v, None, False, 128 ** -0.5, 1024, 1024).astype(
                jnp.float32))
    o, lse = pk.flash_attention_piece(
        q, k, v, True, 128 ** -0.5, 128, 128,
        *((512, qoff) if name == "piece_at_an_offset" else ()))
    return jnp.sum(o.astype(jnp.float32)) + jnp.sum(lse)


UNTOUCHED = ("non_causal_2048", "piece_at_an_offset", "piece_diagonal_chunk")


def _module_without_locations(payload):
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = ir.Context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    ctx.load_all_available_dialects()
    with ctx:
        module = ir.Module.parse(base64.b64decode(payload))
        return module.operation.get_asm(enable_debug_info=False)


def _digest(text):
    bodies = BODY.findall(text)
    normalised = BODY.sub(
        lambda m: hashlib.sha1(_module_without_locations(
            m.group(1)).encode()).hexdigest(), text)
    return hashlib.sha1(normalised.encode()).hexdigest(), len(bodies)


def _lowered_step(main, startup, loss, feeds):
    scope = fluid.Scope()
    for block in (main.global_block(), startup.global_block()):
        for name, var in block.vars.items():
            if var.persistable and all(int(d) >= 0 for d in var.shape):
                scope.set(name, jax.ShapeDtypeStruct(
                    tuple(int(d) for d in var.shape),
                    jnp.dtype(str(var.dtype))))
    traced = build_traced_function(
        main, 0, tuple(sorted(feeds)), [loss], scope, platform="tpu")

    def shaped(n):
        v = scope.find_var(n)
        return jax.ShapeDtypeStruct(v.shape, v.dtype)

    key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
    return jax.jit(traced.fn).trace(
        feeds, {n: shaped(n) for n in traced.ro_names},
        {n: shaped(n) for n in traced.rw_names}, key).lower(
            lowering_platforms=("tpu",))


def _untouched_text(name):
    x = jax.ShapeDtypeStruct((2, 2048, 128), jnp.bfloat16)
    qoff = jax.ShapeDtypeStruct((1,), jnp.int32)
    return jax.jit(jax.grad(
        functools.partial(_untouched, name=name), argnums=(0, 1, 2))).trace(
            x, x, x, qoff).lower(lowering_platforms=("tpu",)).as_text()


def _core_text(t, window):
    """An attention core alone, forward + backward, heads of 128 in blocks
    of 1024.  T = 16384 outgrows the one-kernel backward's dq scratch:
    forward, dq and dk/dv kernels."""
    x = jax.ShapeDtypeStruct((2, t, 128), jnp.bfloat16)
    return jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(pk.flash_attention(
            q, k, v, None, True, 128 ** -0.5, 1024, 1024, window).astype(
                jnp.float32)), argnums=(0, 1, 2))).trace(x, x, x).lower(
                    lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_the_lowered_step_is_what_it_was_before_pr_37(monkeypatch, name):
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.clear_caches()  # an interpreted trace of these shapes would hide
    text = (_core_text(*CORES[name]) if name in CORES
            else _untouched_text(name) if name in UNTOUCHED
            else _lowered_step(*PROGRAMS[name]()).as_text())
    assert _digest(text) == BEFORE[name]
    jax.clear_caches()  # and these would hide from a later interpreted one
