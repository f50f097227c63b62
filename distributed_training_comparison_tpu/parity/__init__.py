"""Eager-parity debugging rail: replay without GSPMD, bisect divergence.

- ``eager``  — any planned layout's train step executed op-by-op (no
  ``jit``, no GSPMD tracing), reusing the real ``_make_step_core`` /
  ``Comms`` transforms so there is no second implementation to drift.
- ``diff``   — the two-gate trajectory diff (bitwise replay gate +
  tolerance-gated eager reference gate) with (step, stage, leaf, ulp)
  localization via the shared ``health/desync`` checksum walk.

Entry points: ``--parity-check N`` (+ ``--parity-tol``) on any run,
``tools/run_report.py --parity`` to render/gate the emitted ``parity``
event; ``tests/test_parity.py`` sweeps the layouts.
"""

from .diff import (
    STAGES,
    ParityCapture,
    StepRecord,
    Tolerance,
    checksum_state,
    corrupt_bitflip,
    f32_bits,
    parse_corrupt,
    run_parity_check,
    ulp_distance,
)
from .eager import (
    EagerComms,
    device_epoch_rows,
    device_step_keys,
    eager_comms_like,
    eager_state_like,
    host_step_key,
    make_eager_step,
)

__all__ = [
    "STAGES",
    "ParityCapture",
    "StepRecord",
    "Tolerance",
    "checksum_state",
    "corrupt_bitflip",
    "f32_bits",
    "parse_corrupt",
    "run_parity_check",
    "ulp_distance",
    "EagerComms",
    "device_epoch_rows",
    "device_step_keys",
    "eager_comms_like",
    "eager_state_like",
    "host_step_key",
    "make_eager_step",
]
