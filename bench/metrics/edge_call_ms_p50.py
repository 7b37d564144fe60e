"""Median host milliseconds spent inside the call that dispatches one
edge batch's program (argument checks, launch), before the wait for its
result, over the traced window."""
import statistics


def read(ctx):
    calls = ctx.spans.durations_ms("edge_call")
    return statistics.median(calls) if calls else None
