"""Share of the forward grid steps of the windowed flash kernels that
compute a tile, in %: where a `fused_attention` op with a `window` takes
the flash kernel, the lowering records at trace time, in
`kernel_tuning.attribution()["attention_band_grid"]`, {"ops": lowerings,
"steps": {"<T>x<window>x<block_q>x<block_k>": [walked, computed]}}: the
grid steps a head's forward walks and the tiles `_band` lets run.  On the
full (BH, nq, nk) grid a 2048 window at T 8192 in 1024-blocks reads
21 / 64 = 32.8 (43 steps a head fetch a K/V block and skip it); on the
band grid 21 / 24 = 87.5 (the three left are the sequence's start, whose
walk repeats a block the pipeline does not fetch again).  Computed over
walked, summed over the recorded shapes.

None where the program records no band grid (a program from before the
counter) or no windowed op took the kernel."""


def read(ctx):
    from paddle_tpu.ops import kernel_tuning

    said = kernel_tuning.attribution().get("attention_band_grid")
    if not said or not said.get("ops") or not said.get("steps"):
        return None
    walked = sum(w for w, _ in said["steps"].values())
    computed = sum(c for _, c in said["steps"].values())
    if not walked:
        return None
    ctx["log"]("band_grid_stat: %d windowed lowerings; forward steps "
               "[walked, computed] a head by TxWxBQxBK: %s"
               % (said["ops"], said["steps"]))
    return 100.0 * computed / walked
