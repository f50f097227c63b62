"""Models: the mean of ``exp(g)`` — the factor a token leaves of a Gated
DeltaNet state — over tokens, value heads, layers and an epoch's steps: the
gauge ``gdn/decay_mean`` on the window's ``metrics`` events (the program's
counter), averaged over the window's epochs.  Near 0 the state forgets
within a token, near 1 it never does; which it is decides how much precision
a long chunk loses.  ``None`` where the program has no such gauge."""


def read(run):
    seen = [
        e["payload"].get("metrics", {}).get("gdn/decay_mean")
        for e in run.clock.in_window("metrics")
    ]
    values = [g["value"] for g in seen if g and g.get("value") is not None]
    return sum(values) / len(values) if values else None
