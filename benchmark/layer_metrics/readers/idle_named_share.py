"""Share of the device's attributed idle seconds that went to a span the
program named itself: entries of the reduced trace's ``idle_gaps``
(benchmark/xplane.py: the longest gaps, each given to the host event that
covers or overlaps it most) whose name starts with one of ``prefixes``, over
all entries listed. A gap that falls to a python frame, to one of jax's own
events or to no host event at all is idleness the program cannot explain."""


def read(sources: dict, params: dict):
    gaps = (sources.get("trace") or {}).get("idle_gaps") or []
    listed = sum(seconds for _, seconds in gaps)
    if not listed:
        return None
    prefixes = tuple(params["prefixes"])
    return sum(seconds for name, seconds in gaps if name.startswith(prefixes)) / listed
