"""Roofline share of the cni_update index kernel (``roofline/cni_update``)."""

from readers import roofline_share


def read(ctx):
    return roofline_share(ctx, "cni_update", ctx.roofline("cni_update"))
