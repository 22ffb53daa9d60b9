"""The plain float32 reference of a configuration's architecture, and
its low-precision control: ``logits`` calls the ``logits`` of the
module the file names (``chipbench/arch``), which imports nothing of
the program and reads only the file and the weights
``chipbench/weights.py`` made."""
from __future__ import annotations

from chipbench import arch


def logits(c: dict, w, tokens, sel, quant: str = "none"):
    """Reference logits (n, V) float32 at rows ``sel`` of the sequence
    ``tokens`` (length a multiple of the module's ``BLOCK_Q``);
    ``quant`` other than ``none`` computes the control."""
    return arch.load(c).logits(c, w, tokens, sel, quant=quant)
