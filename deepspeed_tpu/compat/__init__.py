"""Single import point for the jax symbols whose spelling has moved before.

Written for the one installation there is — jax 0.9.0 / jaxlib 0.9.0 /
libtpu 0.0.34: every entry of ``SHIMMED_SYMBOLS`` holds exactly the spelling
that jax ships, and nothing here probes versions, translates keyword names or
carries a reimplementation for a jax that is not installed.  What remains is
the alias layer itself: the ``direct-shimmed-import`` lint rule bans spelling
these paths anywhere else, so the next upstream rename is one edit here plus a
lint report naming the call sites.  Whether that layer earns its keep is an
open simplicity question (ROADMAP, Design queue).

Exported:

- ``shard_map`` — ``jax.shard_map`` (``check_vma=``, ``axis_names=``).
- ``CompilerParams`` — ``jax.experimental.pallas.tpu.CompilerParams``.
- ``axis_size`` — ``jax.lax.axis_size``.
- ``Space`` — the ``jax.memory.Space`` memories enum.

``SHIMMED_SYMBOLS`` doubles as the machine-readable registry dslint reads —
by AST parse of this file, never by importing it.  Keep values as literal
tuples of literal ``"module:attr"`` strings.
"""

import importlib
from typing import Any, Dict, Tuple

SHIMMED_SYMBOLS: Dict[str, Tuple[str, ...]] = {
    "shard_map": ("jax:shard_map", ),
    "CompilerParams": ("jax.experimental.pallas.tpu:CompilerParams", ),
    "axis_size": ("jax.lax:axis_size", ),
    "Space": ("jax.memory:Space", ),
}


class CompatResolutionError(ImportError):
    """The installed jax lacks the registered spelling of a shimmed symbol."""


_cache: Dict[str, Tuple[Any, str]] = {}


def _resolve_uncached(name: str) -> Tuple[Any, str]:
    try:
        candidates = SHIMMED_SYMBOLS[name]
    except KeyError:
        raise CompatResolutionError(
            f"'{name}' is not a shimmed symbol; known: {', '.join(SHIMMED_SYMBOLS)}")
    tried = []
    for spec in candidates:
        mod_name, _, attr = spec.partition(":")
        try:
            mod = importlib.import_module(mod_name)
        except ImportError:
            tried.append(f"{spec} (module not importable)")
            continue
        obj = getattr(mod, attr, None)
        if obj is not None:
            return obj, spec
        tried.append(f"{spec} (attribute absent)")
    raise CompatResolutionError(
        f"compat: no installed spelling of '{name}' — tried {'; '.join(tried)}. "
        f"The installed jax has moved it; put its current path in "
        f"SHIMMED_SYMBOLS['{name}'].")


def resolve_symbol(name: str, refresh: bool = False) -> Any:
    """The object behind a shimmed name under the installed jax (cached)."""
    if refresh or name not in _cache:
        _cache[name] = _resolve_uncached(name)
    return _cache[name][0]


def resolved_source(name: str) -> str:
    """Which spelling ``resolve_symbol`` bound (for diagnostics)."""
    resolve_symbol(name)
    return _cache[name][1]


# Resolved LAZILY via module __getattr__ (PEP 562): importers that only need
# shard_map (comm, the runtime engine) never trigger a Pallas-TPU import.
def __getattr__(name: str):
    if name in SHIMMED_SYMBOLS:
        return resolve_symbol(name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


__all__ = ["SHIMMED_SYMBOLS", "CompatResolutionError", "resolve_symbol",
           "resolved_source", "shard_map", "CompilerParams", "axis_size", "Space"]
