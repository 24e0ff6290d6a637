"""The fused scan's share of its roofline: the least time the chip could
take for the bytes the scan needs (``chipbench.roofline.scan_bytes``, per
call, averaged over the window's scan calls) at the published HBM peak,
over the device time of the scan's programs in the trace.

The scan is memory-bound (a few operations per byte), so bytes bound it.
Its programs are found by name in the trace's ``XLA Modules`` line."""
from chipbench.roofline import peaks

PROGRAMS = ("jit__eval_partials_fused",)


def read(ctx):
    trace = ctx.get("trace")
    per_call = ctx.get("scan_bytes_per_call")
    if not trace or not per_call:
        return None
    seconds = calls = 0.0
    for name, (secs, n) in trace["programs"].items():
        if name in PROGRAMS:
            seconds += secs
            calls += n
    if not calls:
        return None
    least = calls * per_call / peaks(ctx["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * least / seconds
