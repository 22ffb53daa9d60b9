"""One module per architecture: everything the harness knows about a
model's block.  A configuration file names its module under ``arch``;
``load(cdoc)`` imports ``chipbench.arch.<arch>``.  The generic files
(``weights``, ``reference``, ``correctness``, ``work``, ``bench``,
``run``) dispatch to it and read no width of the file themselves, so a
new architecture comes in as new files alone.

Every module offers:

- ``KERNELS``: kernel name -> regex on the HLO op name it carries in
  the device trace (``chipbench/trace.py``'s ``op_label``).  A kernel
  whose jitted wrapper is renamed is lost from its roofline share.
- ``BLOCK_Q``: the reference's block of query rows; the sequences the
  reference reads are padded to a multiple of it.
- ``specs(cdoc)``: leaf -> ``(shape, std)`` in the program's parameter
  layout.  ``chipbench/weights.py`` draws leaf ``i`` of the flattened
  tree from ``fold_in(key, i)``, so the order of the leaves is part of
  the weights.
- ``logits(cdoc, w, tokens, sel, quant="none")``: the plain float32
  reference, (n, V) logits at rows ``sel`` of ``tokens``; ``quant``
  names the low-precision control.  It imports nothing of the program.
- ``decode_attention(cdoc, lengths)``, ``prefill_attention(cdoc,
  chunks)``: (operations, bytes) of one layer's attention call, and
  ``step_flops(cdoc, dec_lens, chunks)``: the model operations of one
  step (``chipbench/work.py``).
- ``program_config(cdoc)``: the program's ``ModelConfig``, with the
  file's cut applied (``cut`` below), and ``check(cdoc, cfg)``, which
  raises ``ValueError`` where the program's config departs from the
  file.

A configuration file that is cut lists each changed key under
``reduced``, gives its published value under ``published``, and maps
it under ``program.overrides`` to the field of the program's
``ModelConfig`` that takes the file's value.
"""
from __future__ import annotations

import dataclasses
import importlib


def load(cdoc: dict):
    """The architecture module a configuration names."""
    return importlib.import_module(f"chipbench.arch.{cdoc['arch']}")


def _replace(obj, path: list, value):
    head, *rest = path
    if rest:
        value = _replace(getattr(obj, head), rest, value)
    return dataclasses.replace(obj, **{head: value})


def cut(cfg, cdoc: dict):
    """``cfg`` with the file's cut: for each ``program.overrides`` entry
    ``key: field``, the field (``moe.num_experts`` for one in a nested
    group) takes ``cdoc[key]``.  A cut in depth keeps the first layers
    of the program's layer pattern, leading dense layers among them."""
    for key, field in cdoc["program"].get("overrides", {}).items():
        value = cdoc[key]
        if field == "n_layers":
            cfg = dataclasses.replace(
                cfg, n_layers=value, layer_pattern=cfg.layer_pattern[:value])
        else:
            cfg = _replace(cfg, field.split("."), value)
    return cfg
