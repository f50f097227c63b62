"""Models: the fullest held expert's rows over the mean of the held
experts', averaged over an epoch's steps and expert layers — the gauge
``moe/load_max_over_mean`` on the window's ``metrics`` events (the
program's counter; 1.0 is even routing).  ``None`` where the program has
no such gauge."""


def read(run):
    seen = [
        e["payload"].get("metrics", {}).get("moe/load_max_over_mean")
        for e in run.clock.in_window("metrics")
    ]
    values = [g["value"] for g in seen if g and g.get("value") is not None]
    return sum(values) / len(values) if values else None
