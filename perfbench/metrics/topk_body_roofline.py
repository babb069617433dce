"""Roofline share of the top-k program (the job model inside
``ChunkedEvaluator._topk_body``): the least time the chip needs for the
frozen work of one chunk (``perfbench.harness.work``), the larger of
operations over peak FLOP/s and bytes over peak bandwidth, over the device
time of one call of the program in the trace.  The bytes bound binds; the
bf16 peak overstates the vector unit's float32 rate, so the operations bound
is a lower one.  The program is found by its HLO module name, and has to
run once per chip for each chunk."""

from perfbench.harness.trace import program_time
from perfbench.harness.work import topk_body_work

MODULE = "jit__unknown"


def read(run):
    if not run.get("trace") or not run.get("peaks"):
        return None
    calls, seconds = program_time(run, MODULE, "chunk_topk")
    flops, nbytes = topk_body_work(run["rows_per_chunk"], run["swept_keys"])
    n = run["num_devices"]
    bound = max(flops / n / run["peaks"]["flops_per_s"],
                nbytes / n / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * bound / (seconds / calls)
