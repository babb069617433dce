"""Mean time of the program's ``cluster.build_scenarios`` span over a traced
run: the host's building of one planner chunk's scenario batch, its step
cap included, before the rollout is called."""


def read(run):
    samples = (run.get("program") or {}).get("histograms", {}).get("cluster.build_scenarios_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
