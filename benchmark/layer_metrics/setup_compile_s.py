"""Compile cache: sum of ``compile_s`` over the ``compile`` events of
set-up (lowering plus compiling, or plus the cache read on a hit)."""


def read(run):
    return sum(c.get("compile_s") or 0.0 for c in run.setup_compiles)
