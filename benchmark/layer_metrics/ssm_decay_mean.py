"""Models: the mean of ``exp(dt A)`` — the factor a token leaves of a
Mamba-2 state — over tokens, heads, layers and an epoch's steps: the gauge
``ssm/decay_mean`` on the window's ``metrics`` events (the program's
counter), averaged over the window's epochs.  Near 0 the state forgets
within a token, near 1 it never does; which it is decides how much precision
a long chunk loses.  ``None`` where the program has no such gauge."""


def read(run):
    seen = [
        e["payload"].get("metrics", {}).get("ssm/decay_mean")
        for e in run.clock.in_window("metrics")
    ]
    values = [g["value"] for g in seen if g and g.get("value") is not None]
    return sum(values) / len(values) if values else None
