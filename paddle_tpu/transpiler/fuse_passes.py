"""Fusion-pass suite on the OpPattern detector — the parity sweep over the
reference's ir/ fuse passes (framework/ir/fc_fuse_pass.cc,
fuse_elewise_add_act_pass.cc, conv_elementwise_add*_mkldnn_fuse_pass,
seqconv_eltadd_relu_fuse_pass.cc, fc_gru_fuse_pass.cc,
fc_lstm_fuse_pass.cc, embedding_fc_lstm_fuse_pass.cc).

Each pass is an op-level Program rewrite into a fused op whose lowering
already exists — changing WHICH HLO is emitted (fewer, bigger ops with
epilogues attached to the matmul/conv), the same lever the reference's
inference-perf story pulls.  All rewrites are conservative: they require
the exact single-consumer chains the OpPattern matcher guarantees plus
local shape/attr conditions, and leave anything else untouched.  Every
fused target op is differentiable through the generic vjp machinery, so
the fc/elewise passes are train-safe (BuildStrategy.fuse_elewise_add_act_ops).
"""

import paddle_tpu.framework as _fw

from .pass_registry import OpPattern, Pass, register_pass

_ACTS = ("relu", "tanh", "sigmoid")
# fc epilogue activations: the fc lowering's table (nn_ops._mm_act).
# gelu fuses only in its exact-erf default
# form and swish only at beta=1 — _act_fusable checks the attrs.
_FC_ACTS = ("relu", "tanh", "sigmoid", "gelu", "swish")


def _act_fusable(act_op):
    """True when the activation op's attrs match the fused epilogue's
    fixed form (exact gelu, beta-1 swish; the plain acts always do)."""
    if act_op.type == "gelu":
        return not act_op.attrs.get("approximate", False)
    if act_op.type == "swish":
        return float(act_op.attrs.get("beta", 1.0)) == 1.0
    return True


def _mk_op(block, type_, inputs, outputs, attrs):
    op = _fw.Operator(block, type_, None, None, dict(attrs))
    op.inputs = inputs
    op.outputs = outputs
    return op


def _chain_safe(program, chain):
    """A fuse rewrite deletes every intermediate output of the chain; names
    the caller wants fetchable (program._protected_fetch_names, set by the
    ParallelExecutor / predictor before applying passes) must survive."""
    protected = getattr(program, "_protected_fetch_names", None)
    if not protected:
        return True
    for op in chain[:-1]:
        if any(n in protected for n in op.output_arg_names()):
            return False
    return True


def _replace_chain(block, program, chain, new_ops):
    """Swap a matched chain for new ops at the position of the LAST chain
    op (all producers of the fused inputs are defined by then)."""
    idx = block.ops.index(chain[-1]) - (len(chain) - 1)
    _fw.inherit_namescope(chain[-1], *new_ops)
    for op in chain:
        block.ops.remove(op)
    for j, op in enumerate(new_ops):
        block.ops.insert(idx + j, op)
    program._bump_version()


def _bias_of_add(block, add, producer_out):
    """The add operand that is NOT `producer_out`, or None."""
    add_ins = add.inputs.get("X", []) + add.inputs.get("Y", [])
    others = [n for n in add_ins if n != producer_out]
    if producer_out not in add_ins or len(others) != 1:
        return None
    return others[0]


def _is_bias_vector(block, name, want, channel_axis_from_end):
    """True when the var is a length-`want` vector laid out so broadcasting
    against the producer's output applies it along the intended axis: all
    dims 1 except the one `channel_axis_from_end` positions from the end
    (rank may be anything <= that+1).  A numel-only check would accept
    e.g. a [1,1,H,W] positional bias as a per-channel one."""
    v = block._find_var_recursive(name)
    if v is None or v.shape is None:
        return False
    dims = [int(d) for d in v.shape]
    if any(d < 0 for d in dims):
        return False
    n = 1
    for d in dims:
        n *= d
    if n != int(want):
        return False
    # locate the channel axis from the right; a bare [C] vector counts
    # only for k == 0 (it right-broadcasts onto the last axis)
    k = channel_axis_from_end
    if len(dims) <= k:
        return k == 0 and len(dims) == 1
    return dims[len(dims) - 1 - k] == int(want) and all(
        d == 1 for i, d in enumerate(dims) if i != len(dims) - 1 - k)


@register_pass("fc_fuse_pass")
class FcFusePass(Pass):
    """mul + elementwise_add [+ relu/tanh/sigmoid] -> fc
    (ir/fc_fuse_pass.cc)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            mul, add = chain[0], chain[1]
            act = chain[2].type if len(chain) == 3 else ""
            if len(chain) == 3 and not _act_fusable(chain[2]):
                return False
            if int(mul.attrs.get("y_num_col_dims", 1)) != 1:
                return False
            w = block._find_var_recursive(mul.inputs["Y"][0])
            if w is None or w.shape is None or len(w.shape) != 2:
                return False  # fc lowering matmuls Y as-is (no flattening)
            size = int(w.shape[-1])
            bname = _bias_of_add(block, add, mul.outputs["Out"][0])
            if bname is None or not _is_bias_vector(block, bname, size, 0):
                return False
            if not _chain_safe(program, chain):
                return False
            fc = _mk_op(
                block, "fc",
                {"Input": mul.inputs["X"], "W": mul.inputs["Y"],
                 "Bias": [bname]},
                {"Out": [chain[-1].outputs["Out"][0]]},
                {"in_num_col_dims": int(mul.attrs.get("x_num_col_dims", 1)),
                 "activation_type": act},
            )
            _replace_chain(block, program, chain, [fc])
            return True

        n = 0
        for pat in ([["mul", "elementwise_add", a] for a in _FC_ACTS]
                    + [["mul", "elementwise_add"]]):
            n += OpPattern(pat).rewrite(block, fuse)
        program._fc_fused_count = n
        return program


@register_pass("fuse_elewise_add_act_pass")
class FuseElewiseAddActPass(Pass):
    """elementwise_add + activation -> fused_elemwise_activation
    (ir/fuse_elewise_add_act_pass.cc; Unary(Binary(x, y)) convention)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            add, act = chain
            if int(add.attrs.get("axis", -1)) != -1:
                return False  # the fused lowering applies plain + only
            if not _chain_safe(program, chain):
                return False
            fused = _mk_op(
                block, "fused_elemwise_activation",
                {"X": [add.inputs["X"][0]], "Y": [add.inputs["Y"][0]]},
                {"Out": [act.outputs["Out"][0]]},
                {"functor_list": [act.type, "elementwise_add"]},
            )
            _replace_chain(block, program, chain, [fused])
            return True

        n = 0
        for a in _ACTS:
            n += OpPattern(["elementwise_add", a]).rewrite(block, fuse)
        program._elewise_act_fused_count = n
        return program


@register_pass("conv_eltadd_relu_fuse_pass")
class ConvEltaddReluFusePass(Pass):
    """conv2d + elementwise_add(per-channel bias) [+ relu] -> conv2d with
    Bias input and fuse_relu epilogue (conv_bias/conv_relu mkldnn passes
    + fuse_relu_into_conv_pass combined)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            conv, add = chain[0], chain[1]
            relu = chain[2] if len(chain) == 3 else None
            if conv.inputs.get("Bias"):
                return False  # already biased
            f = block._find_var_recursive(conv.inputs["Filter"][0])
            if f is None or f.shape is None:
                return False
            cout = int(f.shape[0])
            bname = _bias_of_add(block, add, conv.outputs["Output"][0])
            if bname is None:
                return False
            # NCHW channel bias arrives either as [*,C,1,1] under plain
            # broadcasting, or as a bare [C] with fluid's axis=1 add
            axis = int(add.attrs.get("axis", -1))
            if axis == 1:
                bv = block._find_var_recursive(bname)
                if (bv is None or bv.shape is None
                        or [int(d) for d in bv.shape] != [cout]):
                    return False
            elif not _is_bias_vector(block, bname, cout, 2):
                return False
            if not _chain_safe(program, chain):
                return False
            conv.inputs["Bias"] = [bname]
            conv.outputs["Output"] = [chain[-1].outputs["Out"][0]]
            if relu is not None:
                conv.attrs["fuse_relu"] = True
            # reposition the conv to the chain tail: its new Bias input may
            # be produced between the conv and the add (e.g. a reshape)
            _replace_chain(block, program, chain, [conv])
            return True

        n = 0
        for pat in (["conv2d", "elementwise_add", "relu"],
                    ["conv2d", "elementwise_add"]):
            n += OpPattern(pat).rewrite(block, fuse)
        program._conv_eltadd_fused_count = n
        return program


@register_pass("seqconv_eltadd_relu_fuse_pass")
class SeqconvEltaddReluFusePass(Pass):
    """sequence_conv + elementwise_add + relu ->
    fusion_seqconv_eltadd_relu (ir/seqconv_eltadd_relu_fuse_pass.cc)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            sc, add, relu = chain
            f = block._find_var_recursive(sc.inputs["Filter"][0])
            if f is None or f.shape is None:
                return False
            nfilt = int(f.shape[-1])
            bname = _bias_of_add(block, add, sc.outputs["Out"][0])
            if bname is None or not _is_bias_vector(block, bname, nfilt, 0):
                return False
            if not _chain_safe(program, chain):
                return False
            inputs = {"X": sc.inputs["X"], "Filter": sc.inputs["Filter"],
                      "Bias": [bname]}
            if sc.inputs.get("SeqLen"):
                inputs["SeqLen"] = sc.inputs["SeqLen"]
            fused = _mk_op(
                block, "fusion_seqconv_eltadd_relu", inputs,
                {"Out": [relu.outputs["Out"][0]]}, sc.attrs,
            )
            _replace_chain(block, program, chain, [fused])
            return True

        n = OpPattern(["sequence_conv", "elementwise_add", "relu"]).rewrite(
            block, fuse)
        program._seqconv_fused_count = n
        return program


def _fuse_fc_into_recurrent(program, rec_types, fused_type):
    """Shared body of fc_gru_fuse_pass / fc_lstm_fuse_pass: an fc (or bare
    mul) producing the recurrent op's Input becomes the WeightX/BiasX
    in-op projection."""
    block = program.global_block()

    def fuse(chain):
        proj, rec = chain
        if rec.inputs.get("WeightX"):
            return False
        if proj.outputs["Out"][0] != rec.inputs["Input"][0]:
            return False
        if not _chain_safe(program, chain):
            return False
        x_in = proj.inputs["Input" if proj.type == "fc" else "X"][0]
        xv = block._find_var_recursive(x_in)
        if xv is None or xv.shape is None or len(xv.shape) != 3:
            return False  # in-op projection is [B, T, D] @ [D, kH]
        if proj.type == "fc":
            if proj.attrs.get("activation_type"):
                return False
            if int(proj.attrs.get("in_num_col_dims", 1)) != 2:
                return False
            rec.inputs["WeightX"] = proj.inputs["W"]
            if proj.inputs.get("Bias"):
                rec.inputs["BiasX"] = proj.inputs["Bias"]
        else:  # bare mul
            if int(proj.attrs.get("x_num_col_dims", 1)) != 2:
                return False
            rec.inputs["WeightX"] = proj.inputs["Y"]
        rec.inputs["Input"] = [x_in]
        rec.type = fused_type
        block.ops.remove(proj)
        program._bump_version()
        return True

    n = 0
    for rec_type in rec_types:
        for head in ("fc", "mul"):
            n += OpPattern([head, rec_type]).rewrite(block, fuse)
    return n


@register_pass("fc_gru_fuse_pass")
def _fc_gru_fuse(program, scope):
    """fc/mul + gru -> fusion_gru (ir/fc_gru_fuse_pass.cc)."""
    program._fc_gru_fused_count = _fuse_fc_into_recurrent(
        program, ("gru", "padded_gru"), "fusion_gru")
    return program


@register_pass("fc_lstm_fuse_pass")
def _fc_lstm_fuse(program, scope):
    """fc/mul + lstm -> fusion_lstm (ir/fc_lstm_fuse_pass.cc)."""
    program._fc_lstm_fused_count = _fuse_fc_into_recurrent(
        program, ("lstm", "padded_lstm"), "fusion_lstm")
    return program


@register_pass("seqexpand_concat_fc_fuse_pass")
class SeqexpandConcatFcFusePass(Pass):
    """sequence_expand(s) + concat + fc/mul -> fusion_seqexpand_concat_fc
    (ir/seq_concat_fc_fuse_pass.cc role on the padded representation).

    Run AFTER fc_fuse_pass: mul+bias+act chains have already collapsed to
    fc, so matching fc (or a bare mul) here covers the general pattern.
    The concat's first input is the [B, T, D] sequence; every further
    input must be a single-consumer sequence_expand of a [B, Di] vector.
    """

    def apply(self, program, scope=None):
        block = program.global_block()
        n = 0
        changed = True
        while changed:
            changed = False
            from ..analysis.graph import consumer_ops, producer_ops

            producers, consumers = producer_ops(block), consumer_ops(block)
            for cat in list(block.ops):
                if cat.type != "concat":
                    continue
                if int(cat.attrs.get("axis", 0)) not in (2, -1):
                    continue
                xs = cat.inputs.get("X", [])
                if len(xs) < 2:
                    continue
                sv = block._find_var_recursive(xs[0])
                if sv is None or sv.shape is None or len(sv.shape) != 3:
                    continue
                expands = []
                for name in xs[1:]:
                    p = producers.get(name)
                    xv = (
                        block._find_var_recursive(p.inputs["X"][0])
                        if p is not None and p.type == "sequence_expand"
                        else None
                    )
                    if (
                        p is None or p.type != "sequence_expand"
                        or xv is None or xv.shape is None
                        or len(xv.shape) != 2
                        or len(consumers.get(name, [])) != 1
                    ):
                        expands = None
                        break
                    expands.append(p)
                if not expands:
                    continue
                cat_out = cat.outputs["Out"][0]
                cons = consumers.get(cat_out, [])
                if len(cons) != 1:
                    continue
                proj = cons[0]
                if proj.type == "fc":
                    if int(proj.attrs.get("in_num_col_dims", 1)) != 2:
                        continue
                    if proj.inputs.get("Input", [None])[0] != cat_out:
                        continue
                    weight = proj.inputs["W"]
                    bias = proj.inputs.get("Bias")
                    act = proj.attrs.get("activation_type") or "identity"
                elif proj.type == "mul":
                    if int(proj.attrs.get("x_num_col_dims", 1)) != 2:
                        continue
                    if int(proj.attrs.get("y_num_col_dims", 1)) != 1:
                        continue
                    if proj.inputs.get("X", [None])[0] != cat_out:
                        continue
                    wv = block._find_var_recursive(proj.inputs["Y"][0])
                    if wv is None or wv.shape is None or len(wv.shape) != 2:
                        continue  # fused lowering matmuls FCWeight as-is
                    weight = proj.inputs["Y"]
                    bias = None
                    act = "identity"
                else:
                    continue
                if act not in ("identity", "relu", "tanh", "sigmoid"):
                    continue
                chain = expands + [cat, proj]
                if not _chain_safe(program, chain):
                    continue
                inputs = {
                    "X": [xs[0]] + [e.inputs["X"][0] for e in expands],
                    "FCWeight": weight,
                }
                if bias:
                    inputs["FCBias"] = bias
                fused = _mk_op(
                    block, "fusion_seqexpand_concat_fc", inputs,
                    {"Out": [proj.outputs["Out"][0]]},
                    {"fc_activation": act},
                )
                # insert at the projection's position (all fused inputs
                # are defined by then); the chain need not be contiguous
                _fw.inherit_namescope(proj, fused)
                block.ops.insert(block.ops.index(proj), fused)
                for op in chain:
                    block.ops.remove(op)
                program._bump_version()
                n += 1
                changed = True
                break
        program._seqexpand_concat_fc_fused_count = n
        return program


@register_pass("embedding_fc_lstm_fuse_pass")
class EmbeddingFcLstmFusePass(Pass):
    """lookup_table + fc/mul + lstm -> fused_embedding_fc_lstm
    (ir/embedding_fc_lstm_fuse_pass.cc)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            lt, proj, lstm = chain
            if proj.outputs["Out"][0] != lstm.inputs["Input"][0]:
                return False
            if lt.attrs.get("padding_idx", -1) not in (-1, None):
                return False
            # the embedding output must be the projection's DATA side —
            # a lookup feeding the weight operand is a different graph
            emb_out = lt.outputs["Out"][0]
            data_slot = "Input" if proj.type == "fc" else "X"
            if proj.inputs.get(data_slot, [None])[0] != emb_out:
                return False
            inputs = {
                "Ids": lt.inputs["Ids"],
                "Embeddings": lt.inputs["W"],
                "WeightH": lstm.inputs["Weight"],
            }
            if proj.type == "fc":
                if proj.attrs.get("activation_type"):
                    return False
                if int(proj.attrs.get("in_num_col_dims", 1)) != 2:
                    return False
                inputs["WeightX"] = proj.inputs["W"]
                if proj.inputs.get("Bias"):
                    inputs["BiasX"] = proj.inputs["Bias"]
            else:
                if int(proj.attrs.get("x_num_col_dims", 1)) != 2:
                    return False
                inputs["WeightX"] = proj.inputs["Y"]
            if not _chain_safe(program, chain):
                return False
            for slot in ("Bias", "SeqLen", "H0", "C0"):
                if lstm.inputs.get(slot):
                    inputs[slot] = lstm.inputs[slot]
            fused = _mk_op(
                block, "fused_embedding_fc_lstm", inputs,
                dict(lstm.outputs), lstm.attrs,
            )
            _replace_chain(block, program, chain, [fused])
            return True

        n = 0
        for rec in ("lstm", "padded_lstm"):
            for head in ("fc", "mul"):
                n += OpPattern(["lookup_table", head, rec]).rewrite(
                    block, fuse)
        program._emb_fc_lstm_fused_count = n
        return program


@register_pass("smooth_label_xent_fuse_pass")
class SmoothLabelXentFusePass(Pass):
    """one_hot -> label_smooth -> softmax_with_cross_entropy(soft_label)
    => ONE smooth_label_xent op reading the raw int labels.

    The reference training-loss idiom (dist_transformer.py builds exactly
    this chain) materializes three [N, V] float arrays — one-hot labels,
    smoothed labels, log-softmax — purely to compute a closed-form
    quantity; on TPU that is pure HBM traffic.  Conservative conditions:
    uniform prior only (no PriorDist), soft_label=True, no ignore_index,
    the xent's Softmax output unused, depth == one_hot attr, and the
    usual single-consumer chain + protected-fetch safety.  Train-safe:
    smooth_label_xent differentiates through the generic vjp."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            oh, smooth, xent = chain
            if not bool(xent.attrs.get("soft_label", False)):
                return False
            if int(xent.attrs.get("ignore_index", -100)) >= 0:
                return False
            if smooth.inputs.get("PriorDist"):
                return False  # closed form assumes the uniform prior
            if not _chain_safe(program, chain):
                return False
            softmax_out = xent.outputs.get("Softmax", [None])[0]
            if softmax_out:
                protected = getattr(program, "_protected_fetch_names", ())
                if softmax_out in protected or _consumers_all_blocks(
                        program, softmax_out, exclude=(xent,)):
                    return False
            # OpPattern's single-consumer scan only covers the global
            # block: a sub-block reading an intermediate would be left
            # dangling by the rewrite
            oh_out = oh.outputs["Out"][0]
            sm_out = smooth.outputs["Out"][0]
            if _consumers_all_blocks(program, oh_out,
                                     exclude=(oh, smooth)):
                return False
            if _consumers_all_blocks(program, sm_out,
                                     exclude=(smooth, xent)):
                return False
            label_name = oh.inputs["X"][0]
            logits_name = xent.inputs["Logits"][0]
            lv = block._find_var_recursive(logits_name)
            # default CLOSED on missing shape info, like every pass here:
            # the unfused chain fails loudly on a depth mismatch; the
            # fused form would compute a plausible wrong loss silently
            if lv is None or lv.shape is None:
                return False
            if int(lv.shape[-1]) != int(oh.attrs.get("depth", -1)):
                return False
            fused = _mk_op(
                block,
                "smooth_label_xent",
                {"Logits": [logits_name], "Label": [label_name]},
                {"Loss": list(xent.outputs["Loss"])},
                {"epsilon": float(smooth.attrs.get("epsilon", 0.0))},
            )
            _replace_chain(block, program, chain, [fused])
            return True

        n = OpPattern(
            ["one_hot", "label_smooth", "softmax_with_cross_entropy"]
        ).rewrite(block, fuse)
        program._smooth_xent_fused_count = n
        return program


def _consumers_all_blocks(program, name, exclude=()):
    """Every op in ANY block reading `name` (sub-block reads count —
    the shared safety scan of the xent/epilogue passes)."""
    return [
        op
        for blk in program.blocks
        for op in blk.ops
        if op not in exclude and name in op.input_arg_names()
    ]


@register_pass("swiglu_fuse_pass")
class SwigluFusePass(Pass):
    """mul(x, Wg) -> swish  alongside  mul(x, Wu), joined by
    elementwise_mul  =>  ONE fused_swiglu op (the gpt2 use_swiglu FFN
    diamond).  The fused op lowers to two f32-accumulated matmuls and
    the gate product (nn_ops._swiglu_dense), which XLA fuses.
    Conservative: beta-1
    swish, same x input and flatten dims on both muls, 2-D same-shape
    weights, single-consumer intermediates (checked across ALL blocks),
    protected fetches respected."""

    def apply(self, program, scope=None):
        block = program.global_block()
        n = 0
        changed = True
        while changed:
            changed = False
            from ..analysis.graph import producer_ops

            producers = producer_ops(block)
            for emul in list(block.ops):
                if emul.type != "elementwise_mul":
                    continue
                if int(emul.attrs.get("axis", -1)) != -1:
                    continue
                xn = emul.inputs.get("X", [None])[0]
                yn = emul.inputs.get("Y", [None])[0]
                if xn is None or yn is None:
                    continue
                hit = None
                for gate_out, up_out in ((xn, yn), (yn, xn)):
                    act = producers.get(gate_out)
                    umul = producers.get(up_out)
                    if (act is None or act.type != "swish"
                            or umul is None or umul.type != "mul"):
                        continue
                    if float(act.attrs.get("beta", 1.0)) != 1.0:
                        continue
                    gmul = producers.get(act.inputs["X"][0])
                    if gmul is None or gmul.type != "mul":
                        continue
                    if gmul.inputs["X"][0] != umul.inputs["X"][0]:
                        continue  # both sides must project the SAME x
                    ncd = int(gmul.attrs.get("x_num_col_dims", 1))
                    if ncd != int(umul.attrs.get("x_num_col_dims", 1)):
                        continue
                    if (int(gmul.attrs.get("y_num_col_dims", 1)) != 1
                            or int(umul.attrs.get("y_num_col_dims", 1))
                            != 1):
                        continue
                    wg = block._find_var_recursive(gmul.inputs["Y"][0])
                    wu = block._find_var_recursive(umul.inputs["Y"][0])
                    if (wg is None or wu is None or wg.shape is None
                            or wu.shape is None or len(wg.shape) != 2
                            or list(wg.shape) != list(wu.shape)):
                        continue
                    # every intermediate single-consumer, ALL blocks
                    inter = [(gmul.outputs["Out"][0], act),
                             (act.outputs["Out"][0], emul),
                             (umul.outputs["Out"][0], emul)]
                    if any(
                        _consumers_all_blocks(program, name) != [consumer]
                        for name, consumer in inter
                    ):
                        continue
                    chain = [gmul, act, umul, emul]
                    if not _chain_safe(program, chain):
                        continue
                    hit = (gmul, act, umul, ncd)
                    break
                if hit is None:
                    continue
                gmul, act, umul, ncd = hit
                fused = _mk_op(
                    block, "fused_swiglu",
                    {"X": [gmul.inputs["X"][0]],
                     "GateW": gmul.inputs["Y"],
                     "UpW": umul.inputs["Y"]},
                    {"Out": [emul.outputs["Out"][0]]},
                    {"x_num_col_dims": ncd},
                )
                # insert at the elementwise_mul's slot: every fused
                # input is defined there; the chain need not be
                # contiguous
                _fw.inherit_namescope(emul, fused)
                block.ops.insert(block.ops.index(emul), fused)
                for op in (gmul, act, umul, emul):
                    block.ops.remove(op)
                program._bump_version()
                n += 1
                changed = True
                break
        program._swiglu_fused_count = n
        return program


@register_pass("residual_ln_fuse_pass")
class ResidualLnFusePass(Pass):
    """elementwise_add(x, y) -> layer_norm  =>  ONE fused_residual_ln op
    whose lowering forms the sum and normalizes it in f32
    (nn_ops._add_ln_dense).  The
    SUM stays a real output under its original name, AND the fused op
    lands at the ADD's position — so every other consumer of the sum
    (gpt2: the add feeds BOTH the norm and the next residual add) reads
    a value defined exactly where it used to be, wherever that consumer
    sits.  Conservative: same-shape known operands (a residual add, not
    a broadcast bias), trailing-axis norm with Scale+Bias, exactly one
    layer_norm consumer of the sum in the global block, protected
    fetches respected."""

    def apply(self, program, scope=None):
        block = program.global_block()
        n = 0
        changed = True
        while changed:
            changed = False
            for add in list(block.ops):
                if add.type != "elementwise_add":
                    continue
                if int(add.attrs.get("axis", -1)) != -1:
                    continue
                xn = add.inputs.get("X", [None])[0]
                yn = add.inputs.get("Y", [None])[0]
                xv = block._find_var_recursive(xn) if xn else None
                yv = block._find_var_recursive(yn) if yn else None
                if (xv is None or yv is None or xv.shape is None
                        or yv.shape is None
                        or list(xv.shape) != list(yv.shape)
                        or any(int(d) < 0 for d in xv.shape[1:])):
                    continue
                add_out = add.outputs["Out"][0]
                cons = _consumers_all_blocks(program, add_out)
                lns = [c for c in cons if c.type == "layer_norm"
                       and c.inputs.get("X", [None])[0] == add_out
                       and c in block.ops]
                if len(lns) != 1:
                    continue
                ln = lns[0]
                rank = len(xv.shape)
                if int(ln.attrs.get("begin_norm_axis", 1)) != rank - 1:
                    continue
                if not (ln.inputs.get("Scale") and ln.inputs.get("Bias")):
                    continue
                chain = [add, ln]
                if not _chain_safe(program, chain):
                    continue
                outputs = {
                    "Sum": [add_out],
                    "Y": list(ln.outputs.get("Y", [])),
                }
                for slot in ("Mean", "Variance"):
                    if ln.outputs.get(slot):
                        outputs[slot] = list(ln.outputs[slot])
                fused = _mk_op(
                    block, "fused_residual_ln",
                    {"X": [xn], "Y": [yn],
                     "Scale": list(ln.inputs["Scale"]),
                     "Bias": list(ln.inputs["Bias"])},
                    outputs,
                    {"epsilon": float(ln.attrs.get("epsilon", 1e-5)),
                     "begin_norm_axis": rank - 1},
                )
                # land at the ADD's index (inputs defined there; Sum
                # defined exactly where it used to be)
                _fw.inherit_namescope(add, fused)
                block.ops.insert(block.ops.index(add), fused)
                block.ops.remove(add)
                block.ops.remove(ln)
                program._bump_version()
                n += 1
                changed = True
                break
        program._residual_ln_fused_count = n
        return program


@register_pass("linear_xent_fuse_pass")
class LinearXentFusePass(Pass):
    """The projected-loss rewrite: the final vocab projection
    (mul, or matmul(transpose_Y) for tied embeddings) feeding
    softmax_with_cross_entropy (hard label) or smooth_label_xent
    becomes ONE fused_linear_xent op, whose lowering owns its backward.
    That is math_ops.linear_xent_tiled: a custom VJP in plain XLA ops
    over row tiles of at most ~256 MiB of f32 logits — no [R, V] array
    in either direction, the logits gradient formed once in the
    operands' dtype for both gradient matmuls (one tile, so [R, V]
    after all, in a GSPMD-partitioned program).  Conservative: 2-D
    weight, hard labels, no ignore_index, the xent's Softmax output
    unused ANYWHERE (all blocks), single-consumer logits, protected
    fetches respected.

    Label contract: OUT-OF-RANGE hard labels (stray pad ids) get zero
    loss and zero gradient after fusion — the fused op's documented
    one_hot convention.  The unfused chains never agreed on this case
    (dense clamps the gather, the softmax_xent kernel yields lse), so
    the pass normalizes an undefined behavior rather than changing a
    defined one; in-range labels are unaffected
    (test_linear_xent_tiled_matches_dense_autodiff draws such labels)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            proj, xent = chain
            if proj.type == "mul":
                if int(proj.attrs.get("y_num_col_dims", 1)) != 1:
                    return False
                w_name, transpose_w = proj.inputs["Y"][0], False
                x_name = proj.inputs["X"][0]
                # the lowering flattens x as [..., H] -> [R, H]: only a
                # mul whose row/contraction split is at the LAST axis
                # matches (x_num_col_dims == rank-1)
                xv = block._find_var_recursive(x_name)
                if xv is None or xv.shape is None:
                    return False
                if int(proj.attrs.get("x_num_col_dims", 1)) != \
                        len(xv.shape) - 1:
                    return False
            else:  # matmul: only the tied-embedding x @ W^T form
                if (not proj.attrs.get("transpose_Y", False)
                        or proj.attrs.get("transpose_X", False)
                        or float(proj.attrs.get("alpha", 1.0)) != 1.0):
                    return False
                w_name, transpose_w = proj.inputs["Y"][0], True
                x_name = proj.inputs["X"][0]
            wv = block._find_var_recursive(w_name)
            if wv is None or wv.shape is None or len(wv.shape) != 2:
                return False
            logits_name = proj.outputs["Out"][0]
            if xent.inputs.get("Logits", [None])[0] != logits_name:
                return False
            if xent.type == "softmax_with_cross_entropy":
                if bool(xent.attrs.get("soft_label", False)):
                    return False
                if int(xent.attrs.get("ignore_index", -100)) >= 0:
                    return False
                softmax_out = xent.outputs.get("Softmax", [None])[0]
                if softmax_out:
                    protected = getattr(
                        program, "_protected_fetch_names", ())
                    if softmax_out in protected or _consumers_all_blocks(
                            program, softmax_out, exclude=(xent,)):
                        return False
                eps = 0.0
            else:  # smooth_label_xent reads raw int labels already
                eps = float(xent.attrs.get("epsilon", 0.0))
            # logits single-consumer across ALL blocks (OpPattern only
            # scans the global block)
            if _consumers_all_blocks(program, logits_name,
                                     exclude=(xent,)):
                return False
            if not _chain_safe(program, chain):
                return False
            fused = _mk_op(
                block, "fused_linear_xent",
                {"X": [x_name], "W": [w_name],
                 "Label": list(xent.inputs["Label"])},
                {"Loss": list(xent.outputs["Loss"])},
                {"epsilon": eps, "transpose_w": transpose_w},
            )
            _replace_chain(block, program, chain, [fused])
            return True

        n = 0
        for head in ("mul", "matmul"):
            for tail in ("softmax_with_cross_entropy", "smooth_label_xent"):
                n += OpPattern([head, tail]).rewrite(block, fuse)
        program._linear_xent_fused_count = n
        return program


_HEADS_AXIS = [0, 2, 1, 3]  # [B, T, H, d] <-> [B, H, T, d], its own inverse


@register_pass("attention_layout_fuse_pass")
class AttentionLayoutFusePass(Pass):
    """transpose2 x 3 -> fused_attention -> transpose2, every transpose the
    heads' ([0, 2, 1, 3])  =>  ONE fused_attention with layout "bthd" on the
    untransposed values: Q, K, V [B, T, H, d] as the projections' reshape2
    leaves them, Out [B, T, H, dv] as the output projection's reshape2 takes
    it (both reshapes stay: they are bitcasts).  The op computes what the
    chain computed (its lowering transposes inside wherever the kernel that
    reads the projections' arrays in place does not engage:
    nn_ops._in_place_engages); what goes is the Program's demand for
    [B, H, T, d] arrays, which cost a TPU-placed step two copies an operand
    where the kernel wants another layout.  Conservative: a training-path
    op (no QStart, layout not yet set), each transposed value read by that
    op alone in ANY block, the op's result read by its transpose alone,
    protected fetches respected.  Run it before minimize: the backward is
    then derived from the rewritten op."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def heads_transpose(op):
            return (op is not None and op.type == "transpose2"
                    and list(op.attrs.get("axis", [])) == _HEADS_AXIS)

        producers = {name: op for op in block.ops
                     for name in op.output_arg_names()}
        n = 0
        for attn in list(block.ops):
            if (attn.type != "fused_attention" or attn.inputs.get("QStart")
                    or attn.attrs.get("layout")):
                continue
            ins = {slot: producers.get(attn.inputs[slot][0])
                   for slot in ("Q", "K", "V")}
            out_name = attn.outputs["Out"][0]
            after = _consumers_all_blocks(program, out_name)
            if (not all(heads_transpose(op) for op in ins.values())
                    or len(after) != 1 or not heads_transpose(after[0])
                    or after[0] not in block.ops):
                continue
            if any(_consumers_all_blocks(program, attn.inputs[slot][0])
                   != [attn] for slot in ins):
                continue
            before = list({id(op): op for op in ins.values()}.values())
            chain = before + [attn, after[0]]
            if not _chain_safe(program, chain):
                continue
            inputs = {slot: list(names)
                      for slot, names in attn.inputs.items()}
            for slot, op in ins.items():
                inputs[slot] = list(op.inputs["X"])
            fused = _mk_op(block, "fused_attention", inputs,
                           {"Out": list(after[0].outputs["Out"])},
                           dict(attn.attrs, layout="bthd"))
            # at the attention's slot: every untransposed value is defined
            # there, and the result's readers all come after its transpose
            _fw.inherit_namescope(attn, fused)
            block.ops.insert(block.ops.index(attn), fused)
            for op in chain:
                block.ops.remove(op)
            program._bump_version()
            n += 1
        program._attention_layout_fused_count = n
        return program


@register_pass("matmul_epilogue_fuse_pass")
def _matmul_epilogue_fuse(program, scope):
    """The training-program epilogue bundle (ROADMAP item 1): fc
    (mul+bias+act), SwiGLU diamonds, and residual-add+layer_norm pairs
    collapse into their fused ops without model edits.  Apply BEFORE
    Optimizer.minimize (grad ops must differentiate through the fused
    ops) and before any AMP rewrite."""
    from .pass_registry import apply_pass

    for name in ("fc_fuse_pass", "swiglu_fuse_pass",
                 "residual_ln_fuse_pass"):
        apply_pass(program, name, scope=scope)
    program._matmul_epilogue_fused_count = (
        getattr(program, "_fc_fused_count", 0)
        + getattr(program, "_swiglu_fused_count", 0)
        + getattr(program, "_residual_ln_fused_count", 0))
    return program
