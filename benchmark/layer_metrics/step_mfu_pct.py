"""Step programs / models: the benchmark's FLOP count (forward + backward,
nothing recomputed) for the traced steps / (device time inside the train
program x the chip's published bf16 peak x chips)."""


def read(run):
    if run.trace_span is None or run.peaks is None:
        return None
    seconds = run.trace_mod.train_seconds(run.trace, *run.trace_span)
    if not seconds:
        return None
    need = (run.traced_steps * run.window["batch_size"]
            * run.train_flops_per_image)
    return 100.0 * need / (seconds * run.peaks["bf16_flops_per_s"] * run.chips)
