"""recall_at_10: share of the exact 10 nearest (reference.exact_knn over
the seed's base) among the 10 returned, over every answer of the window."""


def read(ctx):
    return ctx["checks"]["recall_at_10"]["value"]
