"""Mean time of the program's ``evaluator.prepare`` span over a traced run:
``split_overrides`` and ``pad_block`` of each top-k chunk (the casts of
every override through the device and back)."""


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("evaluator.prepare_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
