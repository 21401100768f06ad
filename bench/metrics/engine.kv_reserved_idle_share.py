"""Engine layer: the share of the KV page pool reserved but holding no
written position, (reserved - written) / pool, from the ``serve.kv_pages``
counter recorded at every traced boundary after admission, averaged."""
from bench import spans as S


def read(ctx):
    kv = [s.args for s in S.named(S.window(ctx), "serve.kv_pages")]
    if not kv:
        return None
    ctx.note(f"engine.kv_reserved_idle_share: {len(kv)} boundaries")
    return 100.0 * sum((a["reserved"] - a["written"]) / a["pool"]
                       for a in kv) / len(kv)
