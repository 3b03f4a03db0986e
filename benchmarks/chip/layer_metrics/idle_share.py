"""Device idle share, %: 1 minus the union of the device's op intervals
over the traced window (averaged over the chips used)."""
from benchmarks.chip import tracing


def read(ctx):
    tr = ctx.get("trace")
    if not tr or not tr["device"]:
        return None
    return 100.0 * (1.0 - tracing.busy_s(tr) / ctx["span_s"])
