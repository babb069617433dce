"""Rows per evaluator chunk the query service issued for the window's
queries (``WhatIfService.summary()``: rows over chunks, counted from the
window's first query to the last one resolved)."""


def read(run):
    svc = run.get("service")
    if not svc or svc["chunks"] == 0:
        return None
    return svc["rows"] / svc["chunks"]
