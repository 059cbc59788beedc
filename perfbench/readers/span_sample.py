"""A mean over a sample of the window's requests of a sum of span times.

spec: ``spans``, a list of [span name, weight]; the value is the mean over
the sampled requests that have every named span of sum(weight * dur_ms)
(a span recorded more than once in a request counts its durations' sum).
"""


def read(spec: dict, run: dict):
    values = []
    for spans in run["spans"]:
        by_name: dict[str, float] = {}
        for sp in spans:
            by_name[sp["name"]] = by_name.get(sp["name"], 0.0) + float(sp["dur_ms"])
        if all(name in by_name for name, _ in spec["spans"]):
            values.append(sum(w * by_name[name] for name, w in spec["spans"]))
    if not values:
        return None
    return sum(values) / len(values)
