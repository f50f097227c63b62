"""Models: the share of the train program's Gated DeltaNet mixer call sites
whose pointwise stages — the convolution, SiLU and l2-norms before the scan,
the gated norm after it — run as ``ops/gdn_pointwise.py``'s fused passes and
not as the composed form that ``gdn_pointwise_plan`` leaves to a call off
the TPU, at a head size of no whole lane tile or at a length of no whole
token tiles — the counts ``gdn_pointwise: {"fused": n, "composed": m}`` on
the train program's ``compile`` events of set-up (the program's counter,
noted while it traced).  ``None`` where no event has the key: a program from
before the counter, or one that traces no such mixer."""


def read(run):
    counts = {}
    for c in run.setup_compiles:
        if str(c.get("name", "")).startswith(run.mix["train_program"]):
            for form, sites in (c.get("gdn_pointwise") or {}).items():
                counts[form] = counts.get(form, 0) + sites
    total = sum(counts.values())
    return 100.0 * counts.get("fused", 0) / total if total else None
