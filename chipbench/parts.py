"""Device time of a model's own parts, in ms per round of a traced window.

The program names parts of a model inside its layers
(``repro.utils.spans.part``): ``model.mamba`` (a Mamba-2 mixer less its
scan), ``model.ssd`` (the chunked scan), ``model.attention``,
``model.mlp`` and ``model.logits`` (the tied unembedding with the loss
or the scores).  The op table a traced run reads from the window's own
round block (``chipbench/layers.py``) carries them as ``scopes``,
``{op: part}``; each op's self time in the window, averaged over the
devices, counts to its part, in training and evaluation alike.  The
parts are whatever names the table carries, so a model with parts of
its own needs only a reader per part.  A program whose table has no
``scopes`` reads nothing here, nor does a part the window never ran.
"""
from __future__ import annotations

from typing import Dict, Optional

from chipbench import layers

COVERED = ("local_train", "eval")


def ms_per_round(ctx) -> Optional[Dict[str, float]]:
    """``{part: ms per round}``, or ``None`` where the program names no
    parts.  Logs the share of local training and evaluation the parts
    cover."""
    tab, tr = ctx.get("op_layers"), ctx["trace"]
    if tab is None or not tr.devices or ctx["rounds"] <= 0:
        return None
    module, table = tab
    scopes = getattr(table, "scopes", None)
    if scopes is None:
        return None
    if "part_ms" not in ctx:
        out: Dict[str, float] = {}
        covered = total = 0.0
        scale = 1e-6 / len(tr.devices) / ctx["rounds"]
        for e in tr.ops():
            if not layers._in_module(e, module):
                continue
            part = scopes.get(e.op)
            if part is not None:
                out[part] = out.get(part, 0.0) + e.self_ns * scale
            if table.get(e.op) in COVERED:
                total += e.self_ns
                covered += e.self_ns if part is not None else 0.0
        ctx["part_ms"] = out
        layers.log(f"[parts] device ms per round {out}; the parts cover "
                   f"{100.0 * covered / max(total, 1.0):.2f}% of "
                   f"local_train + eval")
    return ctx["part_ms"]
