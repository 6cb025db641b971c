"""Share of the render scan's steps that evaluate the field, those with a
hit (the program's `engine_eval_steps / engine_scan_steps`, their change
over the traced window). A step with no hit skips compaction, field
evaluation and scatter."""


def read(ctx):
    c = ctx.get("counters") or {}
    if c.get("engine_scan_steps", 0) <= 0 or "engine_eval_steps" not in c:
        return None
    return 100.0 * c["engine_eval_steps"] / c["engine_scan_steps"]
