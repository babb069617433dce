"""Share of the searches' wall time spent outside ``chunk_topk``: streaming
the grid, the host merge and ``finalize``."""


def read(run):
    search = sum(t1 - t0 for n, t0, t1 in run["spans"] if n == "search")
    chunks = sum(t1 - t0 for n, t0, t1 in run["spans"] if n == "chunk_topk")
    return 100.0 * (1.0 - chunks / search) if search > 0 else None
