"""Score pairs the causal flash kernels compute over the pairs visible, a
ratio: where a causal `fused_attention` op takes the flash kernel, the
lowering records at trace time, in
`kernel_tuning.attribution()["attention_tile_classes"]`, {"ops": lowerings,
"shapes": {"<T>x<window>x<block_q>x<block_k>x<d>": {"ops", "visible",
"fwd_pairs", "bwd_pairs", "fwd_bodies", "bwd_bodies", "tiles"}}}: of one
head, the pairs a causal mask (and a window) leaves visible, the pairs the
forward's and the backward's kernel bodies compute, the copies of the
tile's computation each body holds and the tiles by class (whole, cut by
the diagonal, by the band's edge, by both).  Forward and backward pairs
summed over twice the visible ones, every shape weighted by its lowerings
(a cell's layers together): 2.0 where one 1024-block holds the sequence and
is computed whole (GPT-2), 1.125 on 36 whole tiles of a T = 8192 triangle,
1.0 were only visible pairs computed.

None where the program records no tile classes (a program from before the
counter) or no causal op took the kernel."""


def read(ctx):
    from paddle_tpu.ops import kernel_tuning

    said = kernel_tuning.attribution().get("attention_tile_classes")
    if not said or not said.get("ops") or not said.get("shapes"):
        return None
    shapes = said["shapes"].values()
    visible = sum(s["ops"] * s["visible"] for s in shapes)
    if not visible:
        return None
    computed = sum(s["ops"] * (s["fwd_pairs"] + s["bwd_pairs"])
                   for s in shapes)
    ctx["log"]("tile_class_stat: %d causal flash lowerings; by "
               "TxWxBQxBKxD: %s" % (said["ops"], said["shapes"]))
    return computed / (2.0 * visible)
