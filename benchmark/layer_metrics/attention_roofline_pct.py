"""Kernels: causal attention's share of its roofline over the traced span,
where a Pallas kernel runs it (``setup``'s ``kernel_paths`` say
``attention: pallas``).  The least time the chip could take — the larger of
its operations over the bf16 peak and its bytes over the HBM peak
(``flops/<family>.py attention_flops``, ``attention_bytes``: the lower
triangle only, at the published head size, not the kernel's padded one) —
over the device self-time of the train program's ops under the scope
``attention`` (the kernel with the layout changes and padding around it),
forward, recomputed forward and backward.  ``None`` for the composed path
and for a family without such functions."""

from harness import flops, load_module, scopes


def read(run):
    paths = {
        k: v for c in run.setup_compiles
        if str(c.get("name", "")).startswith(run.mix["train_program"])
        for k, v in (c.get("kernel_paths") or {}).items()
    }
    ms = scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "attention")
    )
    p = run.config["flops"]
    path = flops.FAMILY_DIR / f"{p['family']}.py"
    if (paths.get("attention") != "pallas" or not ms or run.peaks is None
            or not path.is_file()):
        return None
    family = load_module(path)
    if not hasattr(family, "attention_flops"):
        return None
    sequences = run.traced_steps * run.window["batch_size"]
    least = max(
        family.attention_flops(sequences, p) / run.peaks["bf16_flops_per_s"],
        family.attention_bytes(sequences, p) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms / 1e3 * run.traced_steps)
