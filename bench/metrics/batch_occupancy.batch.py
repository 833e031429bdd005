"""Mean share of the engine's lanes that held a request, per decode
window in the measured window (host counter: requests the engine served
in that window, from ``slot_requests`` and ``pop_completed``)."""


def read(run):
    wins = run.windows
    if not wins:
        return None
    return 100.0 * sum(len(w.counts) for w in wins) / (len(wins) * run.lanes)
