"""Mean time of the program's ``evaluator.fetch`` span over a traced run of
the service cell: the copy of every output column of one full-output chunk
to the host, the wait for the device included."""


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("evaluator.fetch_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
