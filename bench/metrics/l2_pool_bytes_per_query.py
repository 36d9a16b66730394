"""l2_pool_bytes_per_query: bytes of candidate pool handed to
``l2_topk_masked`` in the window, s_p·Q·C·d of every launch at the
pool's element width as ``harness.Capture`` records it (Q the launch's
padded rows, C its pool width), over the queries answered."""


def read(ctx):
    launches = ctx["launches"].get("l2_topk_masked")
    answered = len(ctx["window"].q_idx)
    if not launches or not answered:
        return None
    return sum(q * c * d * s_p for q, c, d, _, s_p, _ in launches) / answered
