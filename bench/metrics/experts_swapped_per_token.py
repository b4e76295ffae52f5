"""Experts written into the device's slot pool inside the window, per
token processed there (prompt tokens ingested plus output tokens)."""


def read(ctx):
    tokens = sum(n for _, n, _ in ctx.prompt_pos) + len(ctx.ctx_len)
    return ctx.delta("swap_experts") / tokens if tokens else None
