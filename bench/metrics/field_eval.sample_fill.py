"""Share of the sample slots that field evaluation computed which held an
in-segment sample of a hitting pair (the program's
`engine_samples_processed / engine_sample_slots`, their change over the
traced window): the rest is padding of each step's rung."""


def read(ctx):
    c = ctx.get("counters") or {}
    if c.get("engine_sample_slots", 0) <= 0 or \
            "engine_samples_processed" not in c:
        return None
    return 100.0 * c["engine_samples_processed"] / c["engine_sample_slots"]
