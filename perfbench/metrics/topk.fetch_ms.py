"""Mean time of the program's ``evaluator.fetch`` span over a traced run of
the top-k cell: from the top-k program's return until its six results are
host arrays, so it holds the wait for the device."""


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("evaluator.fetch_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
