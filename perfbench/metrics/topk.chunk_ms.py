"""Mean time of one ``chunk_topk`` call in the window, from the harness's
span around it; the call returns host arrays, so the span ends after the
device finished."""


def read(run):
    spans = [t1 - t0 for name, t0, t1 in run["spans"] if name == "chunk_topk"]
    return 1e3 * sum(spans) / len(spans) if spans else None
