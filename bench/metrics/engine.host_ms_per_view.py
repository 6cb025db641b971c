"""Mean per view of the engine's latency outside its `render` span: queue,
grouping, ordering, ray planning and delivery, from the request spans of
`repro.obs.Tracer`."""


def read(ctx):
    views = ctx["views"]
    if not views or all(v.render_s == 0.0 for v in views):
        return None
    return 1e3 * sum(v.latency_s - v.render_s for v in views) / len(views)
