"""The one door from a configuration to the counts module that knows it.

Every ``harness/counts*.py`` states ``knows(cfg)``, and the guards exclude one
another: exactly one module knows a committed configuration
(``tests/test_counts_for.py``). A reader that needs a family's operations and
bytes asks here and takes them under one set of names: ``decode_step(run)``
for the whole decode step, and for a mixture ``n_mixture_layers``,
``expert_bytes``, ``assignment_flops``, ``per_layer_step``,
``held_assignments_per_token``, ``expert_tokens_per_step``. A new family brings
a counts module and no reader; this file names none of them.
"""

from __future__ import annotations

import glob
import importlib
import os


def modules() -> list:
    """Every counts module beside this file, by file name."""
    here, me = os.path.split(os.path.abspath(__file__))
    names = sorted(os.path.basename(p)[:-3] for p in glob.glob(os.path.join(here, "counts*.py")))
    return [importlib.import_module(f"{__package__}.{n}") for n in names if n + ".py" != me]


def counts_for(cfg: dict):
    """The module that knows ``cfg``; None where none does (the reader then has
    nothing to read). Two that know are a fault of form, not a reading."""
    knowing = [m for m in modules() if m.knows(cfg)]
    if len(knowing) > 1:
        raise ValueError(f"{[m.__name__ for m in knowing]} all know one configuration: make their guards exclusive")
    return knowing[0] if knowing else None


def mixture_counts_for(cfg: dict):
    """``counts_for(cfg)`` where that module counts a mixture of experts (it
    states ``n_mixture_layers`` and the names beside it), else None."""
    family = counts_for(cfg)
    return family if hasattr(family, "n_mixture_layers") else None
