"""Mean time of the program's ``evaluator.dispatch`` span over a traced run:
the call of the top-k program, until it returns its device arrays."""


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("evaluator.dispatch_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
