"""Median of the program's ``service.queue_wait_s`` over a traced run: per
query, from its submission until the service first packs rows of it into a
chunk."""

import statistics


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("service.queue_wait_s")
    return 1e3 * statistics.median(samples) if samples else None
