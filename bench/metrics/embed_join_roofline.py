"""Roofline share of the embed_join join programs (``roofline/embed_join``)."""

from readers import roofline_share


def read(ctx):
    return roofline_share(ctx, "embed_join", ctx.roofline("embed_join"))
