"""Requests the front end refused (`requests_rejected`) over requests sent,
whole load."""


def read(run):
    sent = sum(s.sent is not None for s in run.samples)
    if not sent:
        return None
    return 100.0 * run.load_counters.get("requests_rejected", 0) / sent, sent
