"""Seconds from process start to the first due request: data, build,
warm-up and any compilation."""


def read(ctx):
    return ctx.setup_s
