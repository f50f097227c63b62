"""Checkpoint: seconds the training thread waited for a snapshot, a fetch
or a writer drain (the program's goodput ``ckpt`` phase) inside the window
/ the window.  A share: at the default cadence a window holds few saves."""


def read(run):
    return 100.0 * run.goodput.get("ckpt", 0.0) / run.window["seconds"]
