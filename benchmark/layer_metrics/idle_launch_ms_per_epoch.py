"""Step programs: milliseconds a traced epoch the first device sits idle
while the host launches the train program and waits for its results
(``epoch``'s own time, ``dispatch``, ``compute``): the device's idle
intervals cut at the program's span edges and booked to the innermost span
open (``harness/host_spans.py``, group ``launch``).  ``None`` where the
program draws no ``boundary`` span."""

from harness import host_spans


def read(run):
    return host_spans.idle_ms_per_epoch(run, "launch")
