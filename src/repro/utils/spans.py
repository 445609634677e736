"""Named layers of the round program, and the op-to-layer table.

The round body opens one ``jax.named_scope`` per layer (:func:`layer`):
``fedsim.local_train``, ``fedsim.criteria``, ``fedsim.aggregate``,
``fedsim.adjust`` (Algorithm-1, nested inside ``aggregate``) and
``fedsim.eval`` (the boundary evaluation).  A scope changes only HLO
metadata: XLA carries it into the ``op_name`` of every instruction,
fusions included, built from the ops it wraps, so the compiled program
says which layer each of its instructions belongs to.

Attribution rule: an instruction belongs to the innermost ``fedsim.*``
scope in its ``op_name``; one with none (selection, batch plans, scan
bookkeeping) is :data:`UNSCOPED`.  :func:`op_layers` reads that table
from compiled HLO text (``Compiled.as_text()``), for matching against
the op names of a profiler trace.
"""
from __future__ import annotations

import contextlib
import re
from typing import Dict, Iterator

import jax

PREFIX = "fedsim."
LAYERS = ("local_train", "criteria", "aggregate", "adjust", "eval")
UNSCOPED = "unscoped"

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


@contextlib.contextmanager
def layer(name: str) -> Iterator[None]:
    """Trace the ops built inside under the scope ``fedsim.<name>``."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; have {LAYERS}")
    with jax.named_scope(PREFIX + name):
        yield


def layer_of(op_name: str) -> str:
    """The innermost ``fedsim.*`` layer named in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX) and part[len(PREFIX):] in LAYERS:
            return part[len(PREFIX):]
    return UNSCOPED


def op_layers(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: layer}`` for every instruction of an HLO
    module's text, nested computations (fusion bodies, loop bodies)
    included; an instruction without ``op_name`` metadata is
    :data:`UNSCOPED`."""
    table: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line)
        table[m.group(1)] = layer_of(name.group(1)) if name else UNSCOPED
    return table


def module_name(hlo_text: str) -> str:
    """The name in the text's ``HloModule <name>, ...`` header."""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    return m.group(1) if m else ""
