"""Mean of the program's ``evaluator.evaluate_s`` histogram (``repro.obs``)
over the window: the service evaluates one chunk per call, and the span
closes after every output is on the host."""


def read(run):
    samples = run.get("evaluate_s")
    return 1e3 * sum(samples) / len(samples) if samples else None
