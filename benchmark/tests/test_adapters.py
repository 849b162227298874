"""Each adapter's closed-form operation count against the program's own
walk, and each plain reference against its program's dropout-free forward
loss, at tiny widths on the CPU."""

import functools

import numpy as np
import pytest

from conftest import ONE_CHIP_CELLS, load_cell


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_model_flops_agrees_with_program_flops(cell):
    """utils.flops.program_flops walks the Program IR after the fuse
    passes; the adapter's closed form knows only the shapes.  They count
    the same matmuls, so they agree within 2%.  One known difference, in
    the program's walk: with a tied head the fused_linear_xent_grad op
    carries no transpose_w attribute, so the walk takes the [V, H] weight's
    second dim for V and undercounts the head's backward (8% of a GPT-2
    345M step).  The comparison therefore unties the head, which changes
    no matmul's shape; PERF.md section 7 lists the repair."""
    from paddle_tpu.utils.flops import program_flops

    cfg, work, adapter = load_cell(cell)
    if cfg["model"].get("tie_embeddings"):
        cfg = dict(cfg, model=dict(cfg["model"], tie_embeddings=False))
    main = adapter.build(cfg, work)["main"]
    walked = program_flops(main, batch_hint=int(work["batch"]))
    closed = adapter.model_flops(cfg, work)
    assert walked > 0
    assert abs(closed - walked) / walked < 0.02, (closed, walked)


def test_model_flops_at_published_sizes():
    """The numbers PERF.md quotes: 10.6 TFLOP a Transformer-base step at
    128 x 256 x 256, 19.8 TFLOP a GPT-2 345M step at 8 x 1024, 23.2 GFLOP
    an image for ResNet-50 v1 (3 x 2 x 3.86 GMAC)."""
    def full(cell):
        cfg, work, adapter = load_cell(cell, rehearse=False)
        return adapter.model_flops(cfg, work), work

    flops, _ = full("tfm_base_train")
    assert flops == pytest.approx(10.6e12, rel=0.01)
    flops, work = full("gpt2_345m_train")
    assert flops / work["batch"] == pytest.approx(19.8e12 / 8, rel=0.01)
    flops, work = full("resnet50_train")
    assert flops / work["batch"] == pytest.approx(23.2e9, rel=0.01)


WEIGHT_SCALE = 4.0


@functools.lru_cache(maxsize=None)
def _forward(cell, use_bf16, weight_scale):
    """(adapter, cfg, params, sample, program loss) of a cell's
    dropout-free forward program on freshly initialised weights."""
    import paddle_tpu as fluid

    cfg, work, adapter = load_cell(cell)
    cfg = dict(cfg, train=dict(cfg["train"], use_bf16=use_bf16))
    if use_bf16 and "image_size" in cfg["model"]:
        # batch norm over 8 images of 1 x 1 positions (the rehearsal's last
        # stage) divides by a variance of 8 values and amplifies bf16
        # rounding tenfold; 128 px leaves 4 x 4 positions, nearer the
        # 7 x 7 of the real cell
        cfg = dict(cfg, model=dict(cfg["model"], image_size=128))
    fwd = adapter.build(cfg, work, forward_only=True)
    fwd["startup"].random_seed = 7
    scope = fluid.Scope()
    sample = {k: v[:int(work["reference_rows"])]
              for k, v in adapter.make_batch(cfg, work, 3).items()}
    with fluid.scope_guard(scope):
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fwd["startup"])
        params = []
        for p in fwd["main"].global_block().all_parameters():
            value = np.asarray(scope.find_var(p.name))
            if value.ndim >= 2 and weight_scale != 1.0:
                # at initialisation the loss is ln(classes) whatever the
                # network computes; larger weights make it depend on
                # every layer, so that agreement means something
                value = value * weight_scale
                scope.set(p.name, value)
            params.append((p.name, value))
        got = float(np.asarray(exe.run(
            fwd["main"], feed=sample,
            fetch_list=[fwd["loss"]])[0]).reshape(-1)[0])
    return adapter, cfg, params, sample, got


@pytest.mark.parametrize("use_bf16", [False, True], ids=["f32", "bf16_amp"])
@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_reference_agrees_with_program_forward(cell, use_bf16):
    """Same weights, same rows, dropout-free forward: in float32 the two
    are the same arithmetic (1e-4 relative: reduction order only; weights
    scaled up so that the loss depends on every layer); under the bf16 AMP
    pass, on weights as initialised, they agree within the adapter's own
    tolerance."""
    adapter, cfg, params, sample, got = _forward(
        cell, use_bf16, 1.0 if use_bf16 else WEIGHT_SCALE)
    ref = adapter.reference_loss(cfg, params, sample)
    assert np.isfinite(got) and np.isfinite(ref)
    if use_bf16:
        assert abs(got - ref) <= adapter.TOLERANCE, (got, ref)
    else:
        assert abs(got - ref) <= 1e-4 * abs(ref), (got, ref)


@pytest.mark.parametrize("cell", ONE_CHIP_CELLS)
def test_reference_notices_a_dropped_term(cell):
    """The tolerance is tight enough that a wrong architecture fails: the
    reference given weights in which a normalisation scale is zeroed (the
    first one, or the last one the architecture reads) moves by more than
    the tolerance."""
    adapter, cfg, params, sample, _ = _forward(cell, False, WEIGHT_SCALE)
    good = adapter.reference_loss(cfg, params, sample)
    ones = [i for i, (_, v) in enumerate(params)
            if v.ndim == 1 and np.all(v == 1.0)]
    moved = []
    for i in (ones[0], ones[-1]):
        broken = list(params)
        broken[i] = (params[i][0], np.zeros_like(params[i][1]))
        moved.append(abs(adapter.reference_loss(cfg, broken, sample) - good))
    assert max(moved) > 10 * adapter.TOLERANCE, (good, moved)


def test_batches_come_from_the_seed_alone():
    for cell in ONE_CHIP_CELLS:
        cfg, work, adapter = load_cell(cell)
        a, b, c = (adapter.make_batch(cfg, work, s) for s in (5, 5, 6))
        assert all(np.array_equal(a[k], b[k]) for k in a)
        assert any(not np.array_equal(a[k], c[k]) for k in a)
        assert adapter.work_units(a) > 0
