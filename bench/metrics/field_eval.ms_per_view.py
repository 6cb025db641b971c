"""Device time per view in field evaluation: decode, sample and the colour
MLP (`rtnerf.field_eval`, with the `fused.*` scopes inside it)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    s = tr.scope_s.get("rtnerf.field_eval", 0.0)
    return 1e3 * s / len(ctx["views"]) if s > 0 else None
