"""Program pass infrastructure (framework/ir/pass.h + PassRegistry +
GraphPatternDetector analog).

The reference's IR layer exposes passes as registered, composable
Program-graph rewrites with a declarative subgraph matcher; XLA already
owns low-level fusion on TPU, but the *extension point* — registering a
named Program->Program rewrite and matching op patterns declaratively —
is framework surface users build on (custom quantization, fusion, layout
rewrites).  This module provides:

- ``Pass`` / ``register_pass`` / ``get_pass`` / ``apply_pass`` — the
  PassRegistry contract (ir/pass.h:Pass::Apply, PassRegistry).
- ``OpPattern.match`` — a GraphPatternDetector-lite: matches a linear
  producer chain of op types through the program's def-use graph and
  hands each occurrence to a rewrite callback.
- Built-in registrations for the existing rewrites (bn fold, train-op
  drop, memory plan, bf16 AMP) so ``apply_pass(prog, name)`` works the
  way ``PassBuilder`` exposes passes to Python (pybind.cc:664).
"""

__all__ = [
    "Pass",
    "register_pass",
    "get_pass",
    "list_passes",
    "apply_pass",
    "OpPattern",
]

_PASSES = {}


class Pass:
    """Base class: subclasses implement apply(program, scope=None)."""

    name = None

    def apply(self, program, scope=None):
        raise NotImplementedError

    def __call__(self, program, scope=None):
        return self.apply(program, scope=scope)


def register_pass(name):
    """Decorator registering a Pass subclass or a function
    program -> program under `name` (REGISTER_PASS analog)."""

    def deco(obj):
        if isinstance(obj, type) and issubclass(obj, Pass):
            inst = obj()
            inst.name = name
            _PASSES[name] = inst
        else:
            p = Pass()
            p.name = name
            p.apply = lambda program, scope=None, _f=obj: _f(program, scope)
            _PASSES[name] = p
        return obj

    return deco


def get_pass(name):
    if name not in _PASSES:
        raise KeyError(
            "no pass '%s' registered (known: %s)" % (name, sorted(_PASSES))
        )
    return _PASSES[name]


def list_passes():
    return sorted(_PASSES)


def apply_pass(program, name, scope=None):
    """Apply one registered pass; returns the (possibly same) program.

    Under ``FLAGS_check_program`` the result is statically re-verified
    (analysis.verify_after_pass): verified-in => verified-out becomes a
    structural property of every registry pass, and a pass emitting an
    ill-formed program fails HERE with the pass and offending op named
    instead of at trace time.  Flag off = one flag read, no other cost.
    """
    from ..profiler import phase

    with phase("build.pass", **{"pass": name}):
        out = get_pass(name).apply(program, scope=scope)
    out = out if out is not None else program
    from ..flags import get_flag

    if get_flag("check_program"):
        from ..analysis import verify_after_pass

        verify_after_pass(out, name, scope=scope)
    return out


class OpPattern:
    """GraphPatternDetector-lite: a linear chain of op types connected by
    def-use edges.

        n = OpPattern(["mul", "elementwise_add", "relu"]).rewrite(
                block, lambda ops: fuse(ops))

    The matcher walks the block once, following single-consumer def-use
    links; `rewrite` calls the callback with each matched op list (in
    chain order) and lets it mutate the block (return True to count a
    rewrite)."""

    def __init__(self, op_types):
        self.op_types = list(op_types)

    def _consumer_map(self, block):
        from ..analysis.graph import consumer_map

        return consumer_map(block)

    def match(self, block):
        """Yield lists of Operators matching the chain."""
        consumers = self._consumer_map(block)
        for i, op in enumerate(block.ops):
            if op.type != self.op_types[0]:
                continue
            chain = [op]
            ok = True
            cur = op
            for want in self.op_types[1:]:
                outs = cur.output_arg_names()
                nxt = None
                for name in outs:
                    cs = consumers.get(name, [])
                    # single-consumer edge keeps the rewrite sound (the
                    # intermediate value must not be used elsewhere)
                    if len(cs) == 1 and block.ops[cs[0]].type == want:
                        nxt = block.ops[cs[0]]
                        break
                if nxt is None:
                    ok = False
                    break
                chain.append(nxt)
                cur = nxt
            if ok:
                yield chain

    def rewrite(self, block, fn):
        """Apply fn(list of ops) -> bool to every match; returns count of
        rewrites.  Matches are re-scanned after each mutation, but a chain
        already handed to fn is never re-offered — so attr-tagging
        rewrites that leave the match intact still terminate."""
        count = 0
        seen = set()
        changed = True
        while changed:
            changed = False
            for chain in self.match(block):
                key = tuple(id(op) for op in chain)
                if key in seen:
                    continue
                seen.add(key)
                if fn(chain):
                    count += 1
                    changed = True
                    break  # ops list mutated: re-scan
        return count


# ---------------------------------------------------------------------------
# built-in pass registrations (the PassBuilder default pipeline analog)
# ---------------------------------------------------------------------------
@register_pass("conv_bn_fuse_pass")
def _conv_bn_fuse(program, scope):
    """Back-compat alias of bn_fold_pass (the fold long ago outgrew
    conv: it now also takes fc/mul producers and scale chains) — one
    implementation, two names, so a pipeline listing both cannot
    diverge."""
    return _bn_fold(program, scope)


@register_pass("is_test_pass")
def _is_test(program, scope):
    from .inference_transpiler import InferenceTranspiler

    t = InferenceTranspiler()
    t._drop_train_ops(program)
    return program


@register_pass("bn_fold_pass")
def _bn_fold(program, scope):
    """BN/scale-chain fold into conv2d / depthwise_conv2d / fc / mul
    weights (the generalized inference-transpiler sub-pass; a trailing
    relu is untouched and stays eligible for the conv fuse passes).
    Parity contract: rtol 1e-5 vs the unfused program, >= 1 op dropped
    per folded BN."""
    from .inference_transpiler import InferenceTranspiler

    if scope is None:
        raise ValueError(
            "bn_fold_pass folds BN statistics into producer weights and "
            "needs the scope holding them: apply_pass(prog, "
            "'bn_fold_pass', scope=...)")
    InferenceTranspiler()._fold_batch_norm(program, scope)
    return program


@register_pass("train_prune_pass")
def _train_prune(program, scope):
    """Drop train-only ops (dropout -> is_test form) and, when the
    program carries ``_protected_fetch_names``, slice away everything
    below the inference cut — label slots, loss heads, metric ops.
    Parity contract: protected fetches are value-identical."""
    from .inference_transpiler import InferenceTranspiler

    t = InferenceTranspiler()
    t._drop_train_ops(program)
    t._prune_to_fetches(program)
    return program


@register_pass("weight_int8_pass")
def _weight_int8(program, scope):
    """Weight-only int8 stamping for ANY program (the serving engine's
    quantize_weights_int8 generalized into a registry pass): persistable
    mul/matmul/conv/embedding weights become int8+scale pairs
    dequantized at compute time, f32 originals dropped when dead.
    Parity contract: the documented post-training-quant tolerance
    (tests/test_quant_int8.py)."""
    from ..contrib.quantize import quantize_weights_int8

    if scope is None:
        raise ValueError(
            "weight_int8_pass rewrites weights in the scope: "
            "apply_pass(prog, 'weight_int8_pass', scope=...)")
    quantize_weights_int8(program, scope=scope)
    return program


@register_pass("memory_optimize_pass")
def _memory_optimize(program, scope):
    from .memory_optimization_transpiler import memory_optimize

    memory_optimize(program)
    return program


@register_pass("bf16_amp_pass")
def _bf16_amp(program, scope):
    from ..contrib.mixed_precision import rewrite_bf16

    rewrite_bf16(program)
    return program


@register_pass("nhwc_layout_pass")
def _nhwc_layout(program, scope):
    from .layout_transpiler import rewrite_nhwc

    rewrite_nhwc(program)
    return program


@register_pass("graph_viz_pass")
def _graph_viz(program, scope):
    """ir/graph_viz_pass.cc analog: dump the program's def-use graph as
    graphviz dot.  Output path via program._graph_viz_path (the
    BuildStrategy.debug_graphviz_path plumbing) or ./graph.dot."""
    from ..debugger import draw_block_graphviz

    path = getattr(program, "_graph_viz_path", "") or "./graph.dot"
    draw_block_graphviz(program.global_block(), path=path)
    return program


@register_pass("fuse_relu_into_conv_pass")
class FuseReluIntoConv(Pass):
    """Example fusion built on OpPattern: conv2d followed by a
    single-consumer relu becomes conv2d(act=relu) via the fused-activation
    attr the lowering honors (fuse_elewise_add_act_pass spirit — XLA would
    fuse these anyway; the pass exists as the extension-point demo and to
    shrink the traced op count)."""

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            conv, relu = chain
            out_name = relu.outputs["Out"][0]
            conv.outputs["Output"] = [out_name]
            conv.attrs["fuse_relu"] = True
            block.ops.remove(relu)
            program._bump_version()
            return True

        OpPattern(["conv2d", "relu"]).rewrite(block, fuse)
        return program


@register_pass("attention_fuse_pass")
class AttentionFusePass(Pass):
    """Scaled-dot-product attention fusion (the attention_lstm_fuse_pass
    family analog, aimed at the one pattern XLA cannot collapse into an
    O(T)-memory kernel by itself):

        matmul(Q, K, transpose_Y, alpha)
          [-> elementwise_add(rank-1-in-Tk bias)]
          -> softmax [-> dropout(is_test)]
          -> matmul(weights, V)

    becomes ONE fused_attention op — the flash kernel or its one-tile form
    where platform and shape say so (nn_ops._flash_engages,
    _short_engages), fused XLA otherwise.
    Conservative conditions: single-consumer chain (the matcher
    guarantees it), Q rank-4 [B, H, Tq, Dh], bias with key axis only
    (shape [..., 1, Tk]), softmax over the default last axis,
    inference-mode dropout only.
    """

    def apply(self, program, scope=None):
        block = program.global_block()

        def fuse(chain):
            m1 = chain[0]
            m2 = chain[-1]
            mid = chain[1:-1]
            add = next((o for o in mid if o.type == "elementwise_add"), None)
            sm = next((o for o in mid if o.type == "softmax"), None)
            drop = next((o for o in mid if o.type == "dropout"), None)
            if sm is None:
                return False
            if not m1.attrs.get("transpose_Y", False) or m1.attrs.get(
                "transpose_X", False
            ):
                return False
            if m2.attrs.get("transpose_X") or m2.attrs.get("transpose_Y"):
                return False
            # the probabilities must be matmul2's LHS (weights @ V)
            prob_name = (drop or sm).outputs["Out"][0]
            if m2.inputs.get("X", [None])[0] != prob_name:
                return False
            if sm.attrs.get("axis", -1) not in (-1,):
                return False
            if drop is not None and not drop.attrs.get("is_test", False):
                return False
            # downgrade_in_infer scales the probabilities by (1-p) at
            # inference — fold that into a scale op after the fused kernel
            post_scale = 1.0
            if drop is not None and drop.attrs.get(
                "dropout_implementation", "downgrade_in_infer"
            ) == "downgrade_in_infer":
                post_scale = 1.0 - float(drop.attrs.get("dropout_prob", 0.0))
            qvar = block._find_var_recursive(m1.inputs["X"][0])
            kvar = block._find_var_recursive(m1.inputs["Y"][0])
            vvar = block._find_var_recursive(m2.inputs["Y"][0])
            if any(
                v is None or v.shape is None or len(v.shape) != 4
                for v in (qvar, kvar, vvar)
            ):
                return False

            def _dim(v, i):
                return int(v.shape[i])

            # kernel contract: K/V share Q's batch/head/feature dims and
            # each other's Tk (fused_attention reshapes K/V with Q's b, h,
            # d — MQA-style broadcastable K/V must stay on the matmul path)
            if (
                _dim(kvar, 0) != _dim(qvar, 0)
                or _dim(vvar, 0) != _dim(qvar, 0)
                or _dim(kvar, 1) != _dim(qvar, 1)
                or _dim(vvar, 1) != _dim(qvar, 1)
                or _dim(kvar, 3) != _dim(qvar, 3)
                or _dim(vvar, 3) != _dim(qvar, 3)
                or (_dim(kvar, 2) != -1 and _dim(vvar, 2) != -1
                    and _dim(kvar, 2) != _dim(vvar, 2))
            ):
                return False
            inputs = {
                "Q": m1.inputs["X"],
                "K": m1.inputs["Y"],
                "V": m2.inputs["Y"],
            }
            if add is not None:
                # the bias is whichever add operand is NOT the QK^T product
                prod_name = m1.outputs["Out"][0]
                add_ins = add.inputs.get("X", []) + add.inputs.get("Y", [])
                others = [n for n in add_ins if n != prod_name]
                if prod_name not in add_ins or len(others) != 1:
                    return False
                bname = others[0]
                bvar = block._find_var_recursive(bname)
                # fused Bias contract: reshapeable to [B, Tk] — require
                # [B, 1, 1, Tk] with a per-example batch (dynamic or equal
                # to Q's); broadcast ([1,1,1,Tk]) or per-head biases would
                # crash the fused reshape, leave those graphs alone
                if (
                    bvar is None
                    or bvar.shape is None
                    or len(bvar.shape) != 4
                    or int(bvar.shape[1]) != 1
                    or int(bvar.shape[2]) != 1
                    or (int(bvar.shape[0]) not in (-1,)
                        and int(bvar.shape[0]) != int(qvar.shape[0]))
                    or (int(bvar.shape[3]) != -1 and _dim(kvar, 2) != -1
                        and int(bvar.shape[3]) != _dim(kvar, 2))
                ):
                    return False
                inputs["Bias"] = [bname]
            import paddle_tpu.framework as _fw

            fused = _fw.Operator(
                block,
                "fused_attention",
                None,
                None,
                {
                    "causal": False,
                    "scale": float(m1.attrs.get("alpha", 1.0)),
                },
            )
            fused.inputs = inputs
            out_name = m2.outputs["Out"][0]
            # insert where the SECOND matmul sat: every fused input (incl.
            # a V/Bias produced between the two matmuls) is defined there;
            # the executor runs block.ops strictly in list order
            idx = block.ops.index(m2) - (len(chain) - 1)
            new_ops = [fused]
            if post_scale != 1.0:
                raw = out_name + "@ATTN_RAW"
                ov = block._find_var_recursive(out_name)
                block.create_var(
                    name=raw,
                    shape=list(ov.shape) if ov is not None and ov.shape else None,
                    dtype=ov.dtype if ov is not None else "float32",
                )
                fused.outputs = {"Out": [raw]}
                scale_op = _fw.Operator(
                    block, "scale", None, None,
                    {"scale": post_scale, "bias": 0.0,
                     "bias_after_scale": True},
                )
                scale_op.inputs = {"X": [raw]}
                scale_op.outputs = {"Out": [out_name]}
                new_ops.append(scale_op)
            else:
                fused.outputs = {"Out": [out_name]}
            for op in chain:
                block.ops.remove(op)
            for j, op in enumerate(new_ops):
                block.ops.insert(idx + j, op)
            program._bump_version()
            return True

        n = 0
        for pat in (
            ["matmul", "elementwise_add", "softmax", "dropout", "matmul"],
            ["matmul", "elementwise_add", "softmax", "matmul"],
            ["matmul", "softmax", "dropout", "matmul"],
            ["matmul", "softmax", "matmul"],
        ):
            n += OpPattern(pat).rewrite(block, fuse)
        program._attention_fused_count = n
        return program
