"""Images trained in the window / the window's wall seconds / chips, with
every epoch boundary (validation, snapshot, saves, bookkeeping) inside."""


def read(run):
    return run.window["images"] / run.window["seconds"] / run.chips
