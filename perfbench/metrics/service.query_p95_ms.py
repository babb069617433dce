"""The 95th percentile latency of every query due in the traced window, from
when it was due to be sent until its future resolved.  Reported per layer:
across runs it swings with the host's stalls far more than a bound could
hold (PERF.md §2), while the median stands end to end."""


def read(run):
    return run.get("query_p95_ms")
