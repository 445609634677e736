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
from typing import Dict, Iterator, Optional

import jax

PREFIX = "fedsim."
LAYERS = ("local_train", "criteria", "aggregate", "adjust", "eval")
UNSCOPED = "unscoped"
MODEL_PREFIX = "model."

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_PART = re.compile(r"(?<![\w.])" + re.escape(MODEL_PREFIX)
                   + r"([a-z_][a-z0-9_]*)(?![\w.])")


@contextlib.contextmanager
def layer(name: str) -> Iterator[None]:
    """Trace the ops built inside under the scope ``fedsim.<name>``."""
    if name not in LAYERS:
        raise ValueError(f"unknown layer {name!r}; have {LAYERS}")
    with jax.named_scope(PREFIX + name):
        yield


@contextlib.contextmanager
def part(name: str) -> Iterator[None]:
    """Trace the ops built inside under the scope ``model.<name>``: a part
    of a client model (``mamba``, ``ssd``, ``attention``, ...), any
    lower-case name a model chooses."""
    with jax.named_scope(MODEL_PREFIX + name):
        yield


class OpTable(dict):
    """``{instruction name: layer}``, with ``scopes``: ``{instruction name:
    model part}`` for the instructions inside a ``model.*`` scope."""

    def __init__(self, layers: Dict[str, str], scopes: Dict[str, str]):
        super().__init__(layers)
        self.scopes = scopes


def layer_of(op_name: str) -> str:
    """The innermost ``fedsim.*`` layer named in an ``op_name`` path."""
    for part in reversed(op_name.split("/")):
        if part.startswith(PREFIX) and part[len(PREFIX):] in LAYERS:
            return part[len(PREFIX):]
    return UNSCOPED


def part_of(op_name: str) -> Optional[str]:
    """The innermost ``model.*`` part named in an ``op_name``, if any."""
    found = _PART.findall(op_name)
    return found[-1] if found else None


def op_layers(hlo_text: str) -> OpTable:
    """``{instruction name: layer}`` for every instruction of an HLO
    module's text, nested computations (fusion bodies, loop bodies)
    included; an instruction without ``op_name`` metadata is
    :data:`UNSCOPED`.  Its ``scopes`` name the model part of each
    instruction that has one (:func:`part_of`)."""
    table: Dict[str, str] = {}
    scopes: Dict[str, str] = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            continue
        name = _OP_NAME.search(line)
        table[m.group(1)] = layer_of(name.group(1)) if name else UNSCOPED
        scope = part_of(name.group(1)) if name else None
        if scope is not None:
            scopes[m.group(1)] = scope
    return OpTable(table, scopes)


def module_name(hlo_text: str) -> str:
    """The name in the text's ``HloModule <name>, ...`` header."""
    m = re.search(r"^HloModule\s+([^\s,]+)", hlo_text, re.M)
    return m.group(1) if m else ""
