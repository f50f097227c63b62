"""Kernels: the share of the train program's flash-attention call sites
whose backward is the one fused kernel (``ops/attention.py
_bwd_fused_kernel``) and not the two tiled ones that ``flash_plan`` leaves
to a call whose residents do not fit VMEM — the counts ``flash_backward:
{"fused": n, "tiled": m}`` on the train program's ``compile`` events of
set-up (the program's counter, noted while it traced).  ``None`` where no
event has the key: a program from before the counter, or one that traces
no flash backward."""


def read(run):
    counts = {}
    for c in run.setup_compiles:
        if str(c.get("name", "")).startswith(run.mix["train_program"]):
            for form, sites in (c.get("flash_backward") or {}).items():
                counts[form] = counts.get(form, 0) + sites
    total = sum(counts.values())
    return 100.0 * counts.get("fused", 0) / total if total else None
