"""A Pallas kernel's share of its roofline over the traced span, for the
readers under ``layer_metrics/`` whose kernels are counted per sequence.
The least time the chip could take — the larger of the kernel's operations
over the bf16 peak and its bytes over the HBM peak, both from the
configuration's ``flops/<family>.py`` — over the device self-time of the
train program's ops under one scope: forward, recomputed forward and
backward.  The recomputation's operations are not counted, its time is."""

from . import flops, load_module, scopes


def share(run, kernel, scope, flops_name, bytes_name):
    """100 x least time / measured time, or ``None`` where ``kernel`` did
    not run as a Pallas kernel (``setup``'s ``kernel_paths``), the program
    has no op under ``scope``, or the family has no function of these
    names (each takes the traced sequences and the ``flops`` group)."""
    paths = {
        k: v for c in run.setup_compiles
        if str(c.get("name", "")).startswith(run.mix["train_program"])
        for k, v in (c.get("kernel_paths") or {}).items()
    }
    ms = scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, scope)
    )
    p = run.config["flops"]
    path = flops.FAMILY_DIR / f"{p['family']}.py"
    if (paths.get(kernel) != "pallas" or not ms or run.peaks is None
            or not path.is_file()):
        return None
    family = load_module(path)
    if not (hasattr(family, flops_name) and hasattr(family, bytes_name)):
        return None
    sequences = run.traced_steps * run.window["batch_size"]
    least = max(
        getattr(family, flops_name)(sequences, p) / run.peaks["bf16_flops_per_s"],
        getattr(family, bytes_name)(sequences, p) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms / 1e3 * run.traced_steps)
