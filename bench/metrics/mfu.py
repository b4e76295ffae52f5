"""Model FLOPs the window's tokens needed, over the window, over the chip's
bf16 peak: 2 x the parameters each token uses (only the experts it routes
to), attention over the keys before it, and the output head for each token
that produced a logit the server sampled."""


def read(ctx):
    if ctx.peaks is None:           # no published peak: not on an accelerator
        return None
    f = ctx.model.flops_per_token
    total = sum(f(ctx.cfg, c, head=True) for c in ctx.ctx_len)
    for offset, n, done in ctx.prompt_pos:
        total += sum(f(ctx.cfg, offset + j + 1, head=done and j == n - 1)
                     for j in range(n))
    return 100.0 * total / ctx.seconds / ctx.peaks["bf16_flop_s"]
