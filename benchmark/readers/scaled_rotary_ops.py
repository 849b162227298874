"""rotary_embed ops of the measured Program that carry scaled inverse
frequencies (a `yarn_factor` attribute: what `layers.rotary_embed(scaling=)`
leaves on the op), read from the ops' attributes.  None where there is no
program or it has no rotary_embed op at all; 0 where it has some and none
is scaled (a builder that fell back to plain rotary)."""


def read(ctx):
    main = ctx.get("main")
    if main is None:
        return None
    ops = [op for op in main.global_block().ops if op.type == "rotary_embed"]
    if not ops:
        return None
    return sum(1 for op in ops if op.attrs.get("yarn_factor"))
