"""The benchmark's own yardstick: peaks, FLOP counts, trace reduction, the
window clock and the comparison that decides ``correct``.  Nothing here is
imported by the program, and nothing here imports the program except
``window.py`` and ``compare.py``, which drive it."""

import importlib.util
from pathlib import Path


def load_module(path: Path):
    """Import one of the benchmark's per-name files (a metric's reader, a
    configuration's reference) from its path."""
    spec = importlib.util.spec_from_file_location(
        f"{path.parent.name}_{path.stem}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module
