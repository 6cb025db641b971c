"""Device time per view in the ray renderer's own work: intersection,
active-pair compaction, compositing and scatter (`rtnerf.*` scopes other
than field evaluation)."""

SCOPES = ("rtnerf.intersect", "rtnerf.compact", "rtnerf.composite",
          "rtnerf.scatter")


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    s = sum(tr.scope_s.get(k, 0.0) for k in SCOPES)
    return 1e3 * s / len(ctx["views"]) if s > 0 else None
