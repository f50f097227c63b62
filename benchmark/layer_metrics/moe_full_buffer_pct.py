"""Models: the share of expert-layer calls whose rows overflowed the held
prefix, so that the layer took every held expert over every token instead
(``models/moe.py TopKMoE``), averaged over the window's epochs — the gauge
``moe/full_buffer_share`` on the window's ``metrics`` events (the program's
counter; 0 is the bounded prefix every time).  ``None`` where the program
has no such gauge."""


def read(run):
    seen = [
        e["payload"].get("metrics", {}).get("moe/full_buffer_share")
        for e in run.clock.in_window("metrics")
    ]
    values = [g["value"] for g in seen if g and g.get("value") is not None]
    return 100.0 * sum(values) / len(values) if values else None
