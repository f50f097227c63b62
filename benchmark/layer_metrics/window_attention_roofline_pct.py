"""Kernels: windowed attention's share of its roofline over the traced
span, where a Pallas kernel runs it (``harness/roofline.py``): the band's
visible keys only, the sliding layers only (``flops/<family>.py
window_attention_flops``, ``window_attention_bytes``), over the ops under
the scope ``attention_window``.  ``None`` for the composed path, for a
program with no such scope and for a family without such functions."""

from harness import roofline


def read(run):
    return roofline.share(
        run, "attention", "attention_window",
        "window_attention_flops", "window_attention_bytes",
    )
