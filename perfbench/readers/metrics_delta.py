"""A ratio of /metrics deltas over the window.

spec: ``num`` and ``den``, each a list of [tier, series, weight]; ``scale``.
The value is scale * sum(weight * delta) / sum(weight * delta).
"""


def _total(terms, run):
    total = 0.0
    for tier, series, weight in terms:
        before, after = run["before"].get(tier), run["after"].get(tier)
        if before is None or after is None or series not in after:
            return None
        total += weight * (after[series] - before.get(series, 0.0))
    return total


def read(spec: dict, run: dict):
    num, den = _total(spec["num"], run), _total(spec["den"], run)
    if num is None or not den:
        return None
    return float(spec.get("scale", 1.0)) * num / den
