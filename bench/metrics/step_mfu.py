"""The whole step's share of the chips' peak: the FLOPs the model requires
per token (the configuration's reference counts them from its shapes: no
embedding gather, no recomputation), three times for the forward and the
backward pass, times the traced window's tokens per second, over the
chips' bf16 peak.  Moves ``tokens_per_s``."""


def read(ctx):
    cell = ctx.cell
    per_token = 3 * cell.reference.flops_per_token(cell.config,
                                                   cell.traffic["seq"])
    return 100.0 * per_token * ctx.window["tokens_per_s"] / (
        ctx.peaks["bf16_flops_per_s"] * cell.chips)
