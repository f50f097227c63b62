"""Kernels: the grouped expert matmul's share of its roofline over the
traced span.  The least time the chip could take — the larger of its
operations over the bf16 peak and its bytes over the HBM peak
(``flops/<family>.py moe_gmm_flops``, ``moe_gmm_bytes``, from the rows the
program *counted* on its ``metrics`` events, ``moe/rows``, in the traced
epochs, not the expected rows) — over the device self-time of the train
program's ops under the scope ``moe_gmm``, forward, recomputed forward and
backward.  The recomputation's operations are not counted, its time is:
under ``--remat`` the share cannot reach 100.  ``None`` where the program
counts no rows or the family has no such functions."""

from harness import flops, load_module, scopes


def traced_rows(run):
    """``moe/rows`` summed over the traced epochs' ``metrics`` events."""
    first = run.clock.first_epoch
    epochs = range(first, first + run.clock.trace_epochs)
    counts = [
        e["payload"].get("metrics", {}).get("moe/rows", {}).get("n")
        for e in run.events if e["kind"] == "metrics" and e["epoch"] in epochs
    ]
    counts = [n for n in counts if n is not None]
    return sum(counts) if counts else None


def read(run):
    ms = scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "moe_gmm")
    )
    rows, p = traced_rows(run), run.config["flops"]
    path = flops.FAMILY_DIR / f"{p['family']}.py"
    if not ms or rows is None or run.peaks is None or not path.is_file():
        return None
    family = load_module(path)
    if not hasattr(family, "moe_gmm_flops"):
        return None
    layer_steps = run.traced_steps * (
        len(p["layer_types"]) - p["num_dense_layers"]
    )
    least = max(
        family.moe_gmm_flops(rows, p) / run.peaks["bf16_flops_per_s"],
        family.moe_gmm_bytes(rows, layer_steps, p) / run.peaks["hbm_bytes_per_s"],
    )
    return 100.0 * least / (ms / 1e3 * run.traced_steps)
