"""A number read at paths of the pod's ``/metrics`` dumps the runner keeps.

``after`` names the dump (``metrics_after``, ``metrics_before``,
``trace_span.metrics_after``, ...). ``numerator`` is a list of dotted paths
into it whose values are added; a path that starts with ``-`` is subtracted,
and ``{model}`` stands for the served model's name. With ``before`` every
value is its growth from that dump to ``after`` (a key the earlier dump lacks
counts from 0: the cumulative histograms appear with their first sample).
With ``denominator`` the result is a ratio of two such sums, and ``scale``
multiplies it. A program that has no such key — the parent of the PR that
added the counter — gives ``None``, as does a denominator of 0.
"""


def lookup(tree, path: str):
    for key in path.split("."):
        if not isinstance(tree, dict) or key not in tree:
            return None
        tree = tree[key]
    return tree if isinstance(tree, (int, float)) and not isinstance(tree, bool) else None


def total(sources: dict, params: dict, terms: list[str]):
    after = lookup_dump(sources, params["after"])
    before = lookup_dump(sources, params["before"]) if "before" in params else None
    if after is None or ("before" in params and before is None):
        return None
    out = 0.0
    for term in terms:
        sign = -1.0 if term.startswith("-") else 1.0
        path = term.lstrip("-").replace("{model}", sources.get("model", "default"))
        value = lookup(after, path)
        if value is None:
            return None
        if before is not None:
            value -= lookup(before, path) or 0
        out += sign * value
    return out


def lookup_dump(sources: dict, path: str):
    for key in path.split("."):
        sources = sources.get(key) if isinstance(sources, dict) else None
    return sources if isinstance(sources, dict) else None


def read(sources: dict, params: dict):
    num = total(sources, params, params["numerator"])
    if num is None:
        return None
    if "denominator" in params:
        den = total(sources, params, params["denominator"])
        if not den:
            return None
        num /= den
    return num * params.get("scale", 1.0)
