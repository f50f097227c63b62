"""Process start to the window's opening: imports, data made from the
seed, Trainer init, the first-step comparison, and epoch 0 (compiles or
reads the cache, first validation, first saves)."""


def read(run):
    return run.seconds_to_window
