"""Bytes the evaluator brought to the host (``evaluator.d2h_bytes``) per
query the service admitted (``service.queries``), in KiB, over a traced run."""


def read(run):
    counters = (run.get("program") or {}).get("counters", {})
    if not counters.get("service.queries") or "evaluator.d2h_bytes" not in counters:
        return None
    return counters["evaluator.d2h_bytes"] / 1024 / counters["service.queries"]
