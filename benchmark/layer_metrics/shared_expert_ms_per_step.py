"""Models: self-time a step of the train program's ops under the module
``shared_expert`` (``models/moe.py TopKMoE``: the SwiGLU every token passes
through beside the routed experts), forward, recomputed forward and
backward.  ``None`` where no layer has one."""

from harness import scopes


def read(run):
    return scopes.train_ms_per_step(
        run, lambda op_name: scopes.under(op_name, "shared_expert")
    ) or None
