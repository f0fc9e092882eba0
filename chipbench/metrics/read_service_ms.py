"""Mean host time of a serving round that served at least one read, in
milliseconds: submitting every read that is due to the ``ServingFrontend``
and the one ``serve_reads`` call that answers them (harness timer)."""


def read(rec):
    spans = [b - a for a, b, k in rec.serve_calls if k > 0]
    if not spans:
        return None
    return 1e3 * sum(spans) / len(spans)
