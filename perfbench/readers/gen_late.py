"""How late the load generator ran: a percentile of send time minus due
time over the window's requests, in ms.  spec: ``percentile``."""


def read(spec: dict, run: dict):
    late = sorted(1000.0 * (o.sent_s - o.due_s) for o in run["outcomes"])
    if not late:
        return None
    k = min(len(late) - 1, int(round(float(spec["percentile"]) / 100.0 * (len(late) - 1))))
    return late[k]
