"""Inner blocks the causal flash kernels copy in over the tiles they
compute, a ratio: where a causal `fused_attention` op takes the flash
kernel, the lowering records at trace time, in
`kernel_tuning.attribution()["attention_tile_classes"]`, by shape
"<T>x<window>x<block_q>x<block_k>x<d>", beside the tiles by class the
`fwd_fetches` and `bwd_fetches` of one head: the times its walk of the
forward's grid (k innermost) and of the backward's (q innermost) names
another inner block than at the step before (`pallas_kernels.
_walk_fetches`: the kernels' own index maps, run on the host), which is
when the pipeline copies a block in.  Forward and backward fetches summed
over twice the tiles, every shape weighted by its lowerings (a cell's
layers together).  1.0 where every block copied in is computed on once;
a little under where a block held over a row's end serves two tiles (20 of
21 at T 6144: 0.95); 36 of 21 = 1.71 where every grid step of a T 6144
triangle names its own block, 1.6 at T 4096, 1.78 at 8192; 1.0 where one
block holds the sequence (GPT-2).

None where the program records no fetches (a program from before the
counter) or no causal op took the kernel."""


def read(ctx):
    from paddle_tpu.ops import kernel_tuning

    said = kernel_tuning.attribution().get("attention_tile_classes")
    if not said or not said.get("ops") or not said.get("shapes"):
        return None
    shapes = list(said["shapes"].values())
    if not all("fwd_fetches" in s and "bwd_fetches" in s for s in shapes):
        return None
    tiles = sum(s["ops"] * sum(s["tiles"].values()) for s in shapes)
    if not tiles:
        return None
    fetches = sum(s["ops"] * (s["fwd_fetches"] + s["bwd_fetches"])
                  for s in shapes)
    ctx["log"]("block_fetch_stat: by TxWxBQxBKxD [fwd, bwd fetches, "
               "tiles]: %s" % {k: [s["fwd_fetches"], s["bwd_fetches"],
                                   sum(s["tiles"].values())]
                               for k, s in said["shapes"].items()})
    return fetches / (2.0 * tiles)
