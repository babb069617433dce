"""Share of the wave rollout's lane-steps that did work over a traced run:
``vector_sim.lane_steps`` (each lane's own loop iterations) over
``vector_sim.loop_steps`` (per device, its lanes times its slowest lane's
iterations, which every lane of the vmapped ``while_loop`` runs)."""


def read(run):
    counters = (run.get("program") or {}).get("counters", {})
    if not counters.get("vector_sim.loop_steps"):
        return None
    return 100.0 * counters["vector_sim.lane_steps"] / counters["vector_sim.loop_steps"]
