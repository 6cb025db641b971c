"""Share of its roofline that field evaluation reaches: the least time the
chip could take for the required field and MLP operations of the traced
views (bench/shapes.py), over the device time in `rtnerf.field_eval`.
The required bytes are tiny, so the compute bound binds."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["peak"] is None:
        return None
    t = tr.scope_s.get("rtnerf.field_eval", 0.0) / tr.n_devices
    if t <= 0:
        return None
    ops = ctx["required_samples"] * ctx["field_ops_per_sample"]
    least = max(ops / ctx["peak"]["flops_per_s"],
                ctx["bytes_per_view"] / ctx["peak"]["hbm_bytes_per_s"])
    return 100.0 * least / t
