"""The whole render's share of the chips' peak: the required operations of
the window's views (samples x operations per sample, bench/shapes.py) per
second of the window, over chips x peak."""


def read(ctx):
    if ctx["peak"] is None or ctx["window_s"] <= 0:
        return None
    ops = ctx["required_samples"] * ctx["ops_per_sample"]
    return 100.0 * ops / (ctx["window_s"] * ctx["chips"]
                          * ctx["peak"]["flops_per_s"])
