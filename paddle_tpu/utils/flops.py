"""Analytic FLOPs accounting + chip peak lookup for MFU reporting.

The reference had no MFU notion (its benchmarks report images/sec only,
benchmark/IntelOptimizedPaddle.md); on TPU the north-star metric is model
FLOPs utilization, so the bench harness walks the Program IR, sums the
matmul/conv FLOPs from compile-time shapes, and divides achieved
FLOPs/sec by the chip's peak (contrib/memory_usage_calc.py is the closest
reference analog of this kind of static program accounting).
"""

import numpy as np

__all__ = ["program_flops", "chip_peak_flops", "mfu"]


def _shape(block, name, batch_hint):
    v = block._find_var_recursive(name)
    if v is None or v.shape is None:
        return None
    return tuple(
        batch_hint if d in (-1, None) else int(d) for d in v.shape
    )


def _prod(xs):
    out = 1
    for x in xs:
        out *= x
    return out


def program_flops(program, batch_hint=1):
    """Analytic forward+backward FLOPs for one execution of the program.

    Counts the matmul-class ops (where essentially all TPU FLOPs live:
    conv2d, mul/fc, matmul) from IR shapes; elementwise/norm traffic is
    bandwidth, not FLOPs, and is ignored.  Backward ops are counted as 2x
    their forward op (the standard dL/dW + dL/dX accounting), so a training
    program (which contains `*_grad` ops) lands at ~3x forward.
    Unknown (-1) dims resolve to `batch_hint`.
    """
    total = 0.0
    blk = program.global_block()
    for op in blk.ops:
        t = op.type
        grad = False
        if t.endswith("_grad"):
            t = op.attrs.get("__fwd_type__", t[: -len("_grad")])
            grad = True
        factor = 2.0 if grad else 1.0
        if t == "conv2d":
            # grad ops carry the fwd output shape via the Output@GRAD input
            out_names = (
                op.outputs.get("Output")
                or op.outputs.get("Out")
                or op.inputs.get("Output@GRAD")
                or op.inputs.get("Out@GRAD")
                or [""]
            )
            out = _shape(blk, out_names[0], batch_hint)
            flt = _shape(blk, op.inputs.get("Filter", [""])[0], batch_hint)
            if not out or not flt or len(out) != 4 or len(flt) != 4:
                continue
            n, co, ho, wo = out
            _, cin_g, kh, kw = flt
            total += factor * 2.0 * n * co * ho * wo * cin_g * kh * kw
        elif t == "conv2d_transpose":
            inp = _shape(blk, op.inputs.get("Input", [""])[0], batch_hint)
            flt = _shape(blk, op.inputs.get("Filter", [""])[0], batch_hint)
            if not inp or not flt or len(inp) != 4 or len(flt) != 4:
                continue
            n, cin, hi, wi = inp
            _, co_g, kh, kw = flt
            total += factor * 2.0 * n * cin * hi * wi * co_g * kh * kw
        elif t in ("mul", "fc", "fused_swiglu"):
            x_slot = "Input" if t == "fc" else "X"
            y_slot = ("W" if t == "fc"
                      else "GateW" if t == "fused_swiglu" else "Y")
            x = _shape(blk, op.inputs.get(x_slot, [""])[0], batch_hint)
            y = _shape(blk, op.inputs.get(y_slot, [""])[0], batch_hint)
            if not x or not y:
                continue
            ncd = int(op.attrs.get(
                "in_num_col_dims" if t == "fc" else "x_num_col_dims", 1))
            m = _prod(x[:ncd])
            k = _prod(x[ncd:])
            n2 = _prod(y[1:]) if len(y) > 1 else 1
            # SwiGLU runs TWO projections (gate + up) per op
            total += factor * 2.0 * m * k * n2 * (
                2.0 if t == "fused_swiglu" else 1.0)
        elif t == "fused_linear_xent":
            # the folded final projection: [R, H] @ [H, V]
            x = _shape(blk, op.inputs.get("X", [""])[0], batch_hint)
            w = _shape(blk, op.inputs.get("W", [""])[0], batch_hint)
            if not x or not w or len(w) != 2:
                continue
            m = _prod(x[:-1])
            k = x[-1]
            # a grad op carries its forward's attrs under __fwd_attrs__
            attrs = op.attrs.get("__fwd_attrs__", op.attrs)
            n2 = w[0] if attrs.get("transpose_w", False) else w[1]
            total += factor * 2.0 * m * k * n2
        elif t == "moe_ffn":
            # the router, and the two grouped matmuls over the N * top_k
            # rows that are routed: [., d] x [d, 2f] and [., f] x [f, d]
            x = _shape(blk, op.inputs.get("X", [""])[0], batch_hint)
            wr = _shape(blk, op.inputs.get("RouterW", [""])[0], batch_hint)
            wd = _shape(blk, op.inputs.get("DownW", [""])[0], batch_hint)
            if not x or not wr or not wd:
                continue
            rows, d = _prod(x[:-1]), x[-1]
            # a grad op carries its forward's attrs under __fwd_attrs__
            attrs = op.attrs.get("__fwd_attrs__", op.attrs)
            # (a chip's share holds wd[0] of the router's wr[-1] experts
            # and expects that share of the routed rows)
            routed = rows * int(attrs["top_k"]) * wd[0] / float(wr[-1])
            # an ungated expert (relu2) is two [d, f] matmuls, not three
            mats = 2 if attrs.get("expert_act") == "relu2" else 3
            total += factor * 2.0 * (rows * d * wr[-1]
                                     + routed * mats * d * wd[1])
        elif t == "short_conv":
            # no matmul: B * u, L multiply-adds and the C gate a value
            x = _shape(blk, op.inputs.get("BCX", [""])[0], batch_hint)
            k = _shape(blk, op.inputs.get("Filter", [""])[0], batch_hint)
            if not x or not k:
                continue
            total += factor * (2.0 * k[1] + 2.0) * _prod(x[:-1]) * k[0]
        elif t in ("kda_attention", "gated_delta_attention"):
            # the chunkwise form at C = 64, the triangular solve's C^3
            # left out: a token a head three [C, dk] and two [C, dv]
            # products against the chunk (A_kk, A_qk and the solve's W;
            # the solve's U0 and A_qk U) and three dk x dv ones against
            # the state (W S, Q S, K^T U)
            q = _shape(blk, op.inputs.get("Q", [""])[0], batch_hint)
            v = _shape(blk, op.inputs.get("V", [""])[0], batch_hint)
            if not q or not v or len(q) != 4:
                continue
            from ..ops.kda_ops import CHUNK
            # a VALUE head a token (gated_delta_attention's key heads are
            # fewer: no credit for a product two value heads could share)
            (b, _, tq, dk), h = q, v[1]
            total += factor * b * h * tq * (
                2.0 * CHUNK * (3 * dk + 2 * v[-1]) + 6.0 * dk * v[-1])
        elif t == "mamba2_scan":
            # the chunkwise form at Q = 128: a token a group C B^T against
            # its chunk (2 Q N), a head the masked product (2 Q P) and the
            # state read and written (4 N P); the exponentials left out
            x = _shape(blk, op.inputs.get("X", [""])[0], batch_hint)
            bm = _shape(blk, op.inputs.get("B", [""])[0], batch_hint)
            if not x or not bm or len(x) != 4:
                continue
            from ..ops.mamba2_ops import CHUNK as q
            (b, h, tq, p), (g, n) = x, (bm[1], bm[-1])
            total += factor * b * tq * (
                g * 2.0 * q * n + h * (2.0 * q * p + 4.0 * n * p))
        elif t == "matmul":
            x = _shape(blk, op.inputs.get("X", [""])[0], batch_hint)
            y = _shape(blk, op.inputs.get("Y", [""])[0], batch_hint)
            if not x or not y:
                continue
            tx = bool(op.attrs.get("transpose_X", False))
            ty = bool(op.attrs.get("transpose_Y", False))
            m = x[-1] if tx else x[-2] if len(x) > 1 else 1
            k = x[-2] if tx else x[-1]
            n2 = y[-2] if ty else y[-1] if len(y) > 1 else 1
            batch = _prod(x[:-2]) if len(x) > 2 else 1
            total += factor * 2.0 * batch * m * k * n2
        elif t == "fused_attention":
            # QK^T + PV: 2 matmuls of [B*H, Tq, d] x [B*H, d, Tk]
            q = _shape(blk, op.inputs.get("Q", [""])[0], batch_hint)
            k = _shape(blk, op.inputs.get("K", [""])[0], batch_hint)
            if not q or not k or len(q) != 4:
                continue
            # a grad op carries its forward's attrs under __fwd_attrs__
            attrs = op.attrs.get("__fwd_attrs__", op.attrs)
            if (attrs.get("layout") or "bhtd") == "bthd":  # [B, T, H, d]
                q, k = ([s[0], s[2], s[1], s[3]] for s in (q, k))
            b, h, tq, d = q
            tk = k[2]
            v = _shape(blk, op.inputs.get("V", [""])[0], batch_hint)
            dv = v[-1] if v and len(v) == 4 else d  # latent: 192 over 128
            window = int(attrs.get("window", 0) or 0)
            if window:  # sliding window: compute scales with the band
                tk = min(tk, window)
            total += factor * 2.0 * b * h * tq * tk * (d + dv)
    return total


# bf16 peak FLOPs/sec per chip generation (public spec sheets)
_PEAKS = {
    "v2": 45e12,
    "v3": 123e12,
    "v4": 275e12,
    "v5e": 197e12,
    "v5 lite": 197e12,
    "v5litepod": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "trillium": 918e12,
}


def chip_peak_flops(device):
    """Peak bf16 FLOPs/sec of `device`'s chip generation; None on a CPU
    device (a host run reports raw throughput without an MFU claim).  A
    non-CPU device_kind missing from _PEAKS raises: a peak guessed for an
    unknown chip would put a wrong MFU under a device metric name."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, peak in _PEAKS.items():
        if key in kind:
            return peak
    raise ValueError(
        "chip_peak_flops: no peak recorded for device_kind %r (platform "
        "%s) — add it to utils.flops._PEAKS with its source"
        % (device.device_kind, device.platform))


def mfu(flops_per_step, steps, seconds, device):
    peak = chip_peak_flops(device)
    if not peak or seconds <= 0:
        return None
    return flops_per_step * steps / seconds / peak
