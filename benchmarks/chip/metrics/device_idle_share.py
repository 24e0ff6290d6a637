"""Share of the traced slice of the window in which no operation ran on the
chip: 1 - (union of device-op intervals) / (traced seconds), from the
profiler trace (``chipbench.tracing.reduce``)."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
