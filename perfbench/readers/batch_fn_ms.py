"""Mean host time of the scorer's batch function (`batch_predict`) over the
window, from the benchmark's span round it."""


def read(run: dict, args: dict):
    spans = run.get("batch_spans")
    if not spans:
        return None
    return 1e3 * sum(t1 - t0 for t0, t1, _ in spans) / len(spans)
