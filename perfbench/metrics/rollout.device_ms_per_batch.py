"""Device time of the wave-rollout program per ``simulate_batch`` call (one
per planner chunk), from the trace, per chip.  The program is found by its
HLO module name, and has to run once per chip for each chunk."""

from perfbench.harness.trace import program_time

MODULE = "jit_per_device"


def read(run):
    if not run.get("trace"):
        return None
    calls, seconds = program_time(run, MODULE, "chunk_topk")
    return 1e3 * seconds / calls
