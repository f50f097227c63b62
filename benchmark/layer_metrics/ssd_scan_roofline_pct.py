"""Kernels: the state-space scan's share of its roofline over the traced
span, whatever ``kernel_paths`` says ran it (composed XLA or a kernel: the
count is of the work, ``flops/<family>.py ssd_scan_flops`` and
``ssd_scan_bytes``, the chunked form at the configuration's ``ssd_chunk``).
The least time the chip could take — the larger of the operations over the
bf16 peak and the bytes over the HBM peak — over the device self-time of
the train program's ops under the scope ``ssd_scan``, forward, recomputed
forward and backward.  The recomputation's operations are not counted, its
time is: under ``--remat`` the share cannot reach 100.  ``None`` where the
program has no op under the scope or the family has no such functions.
(``harness/roofline.py share`` answers ``None`` for a path that is not
``pallas``, so the division is made here, as ``gdn_scan_roofline_pct``
makes it.)"""

from harness import flops, load_module, scopes


def read(run):
    ms = scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "ssd_scan")
    )
    p = run.config["flops"]
    path = flops.FAMILY_DIR / f"{p['family']}.py"
    if not ms or run.peaks is None or not path.is_file():
        return None
    family = load_module(path)
    if not (hasattr(family, "ssd_scan_flops") and hasattr(family, "ssd_scan_bytes")):
        return None
    sequences = run.traced_steps * run.window["batch_size"]
    least = max(
        family.ssd_scan_flops(sequences, p) / run.peaks["bf16_flops_per_s"],
        family.ssd_scan_bytes(sequences, p) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms / 1e3 * run.traced_steps)
