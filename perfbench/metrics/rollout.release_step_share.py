"""Share of the wave rollout's lane-steps spent releasing DAG jobs over a
traced run: ``vector_sim.release_steps`` (the steps, summed over lanes, in
which the clock stood still because a job was freed by its parents) over
``vector_sim.lane_steps`` (each lane's own loop iterations)."""


def read(run):
    counters = (run.get("program") or {}).get("counters", {})
    if not counters.get("vector_sim.lane_steps") or "vector_sim.release_steps" not in counters:
        return None
    return 100.0 * counters["vector_sim.release_steps"] / counters["vector_sim.lane_steps"]
