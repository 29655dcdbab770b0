"""Shared Pallas dispatch policy for the ops kernels.

One flag + one predicate, imported by flash/fused_adam/quantize so tests can
monkeypatch a single module and dispatch-policy changes happen in one place.
``kernel_calls`` answers which kernels a lowered or compiled program holds, so
a run on the chip can prove that no XLA reference stood in for one.
"""

import collections
import re
from typing import Dict

import jax

INTERPRET = False  # flipped by tests / debugging


def use_pallas() -> bool:
    return INTERPRET or jax.default_backend() == "tpu"


# lowered text carries kernel_name; compiled text only the op_name path, whose
# last scope is the kernel's name, wrapped in jvp()/transpose() under autodiff
_KERNEL_NAME = re.compile(r'kernel_name = "([^"]+)"|op_name="[^"]*?(\w+)\)*/pallas_call"')


def kernel_calls(program_text: str) -> Dict[str, int]:
    """Mosaic kernel calls in ``lowered.as_text()`` or ``compiled.as_text()``,
    counted by the ``name=`` each ``pallas_call`` was given.  Interpret-mode
    kernels are plain XLA and do not appear."""
    counts: Dict[str, int] = collections.Counter()
    for line in program_text.splitlines():
        if "tpu_custom_call" not in line:
            continue
        m = _KERNEL_NAME.search(line)
        counts[(m.group(1) or m.group(2)) if m else "unnamed"] += 1
    return dict(counts)
