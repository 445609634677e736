"""Per-layer device time and host-attributed idle of a traced window.

The program names its layers (``repro.utils.spans``): the round block's
ops carry ``fedsim.<layer>`` scopes in their HLO metadata, and ``run``
opens ``fedsim.*`` host spans on the profiler's clock around each block
(``block``), its dispatch (``dispatch``), the metric pull (``pull``), the
DP budget check (``dp_check``) and the checkpoint write (``checkpoint``).

* Device time: each op's self time in the window (``chipbench/trace.py``),
  averaged over the devices and summed by the layer the op-to-layer
  table of the compiled round block gives it.  An op of another module,
  or one the table does not name, is ``unscoped``.  An op whose event
  carries no ``hlo_module`` stat is looked up by name alone.
* Idle: every stretch of the window in which no op ran on a device is
  split, time-weighted, by the innermost ``fedsim.*`` host span that
  covers each part of it (``none`` where no such span does), and
  averaged over the devices.

The table is read from the program itself (``FederatedSimulation.
op_layers``): a traced run asks the window's own simulation for it once
the trace has stopped, and hands it to the readers as
``ctx["op_layers"]``.  A program without that method or without the
host spans reads nothing here.
"""
from __future__ import annotations

import bisect
import sys
import time
import traceback
from typing import Dict, List, Optional, Tuple

from chipbench.trace import Trace, merge

# the program's names (``repro.utils.spans``), kept here as well: the
# benchmark also runs against a program that has no such module
PREFIX = "fedsim."
LAYERS = ("local_train", "criteria", "aggregate", "adjust", "eval")
UNSCOPED = "unscoped"
NONE = "none"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# -- the op-to-layer table -------------------------------------------------
def build_table(sim, config: dict) -> Optional[Tuple[str, Dict[str, str]]]:
    """``(module, {op: layer})`` of ``sim``'s compiled round block, traced at
    the configuration's precision as the window's run was, or ``None``
    where the program cannot say.  A failure is logged, and the run goes
    on with the readers that need the table reading nothing."""
    if not hasattr(sim, "op_layers"):
        return None
    from chipbench import precision
    from chipbench.harness import CompileCounter

    counter = CompileCounter()
    t0 = time.perf_counter()
    try:
        with precision.program(config):
            tab = sim.op_layers()
    except Exception:
        log("[layers] no op-to-layer table:\n" + traceback.format_exc())
        return None
    finally:
        counter.close()
    log(f"[layers] table built in {time.perf_counter() - t0:.1f} s; "
        f"executables built or loaded {counter.builds}, compilation cache "
        f"misses {counter.misses}")
    return tab


def _in_module(e, module: str) -> bool:
    mod = e.stats.get("hlo_module")
    return mod is None or mod == module


def log_coverage(tr: Trace, module: str, tab: Dict[str, str]) -> None:
    ops = [e for e in tr.ops() if _in_module(e, module)]
    named = [e for e in ops if e.op in tab]
    tot = sum(e.self_ns for e in ops) or 1.0
    stats = sorted(ops[0].stats) if ops else []
    log(f"[layers] module {module}: {len(named)} of {len(ops)} op events "
        f"({100.0 * sum(e.self_ns for e in named) / tot:.3f}% of their "
        f"self time) named in its table of {len(tab)}; op stats {stats}")


# -- device time by layer --------------------------------------------------
def layer_seconds(tr: Trace, module: str,
                  tab: Dict[str, str]) -> Dict[str, float]:
    """Op self time in the window by layer, averaged over the devices;
    the values sum to the window's summed op self time."""
    out = dict.fromkeys(LAYERS + (UNSCOPED,), 0.0)
    if not tr.devices:
        return out
    n = len(tr.devices)
    for e in tr.ops():
        lay = tab.get(e.op, UNSCOPED) if _in_module(e, module) else UNSCOPED
        out[lay] += e.self_ns * 1e-9 / n
    return out


def device_ms_per_round(ctx) -> Optional[Dict[str, float]]:
    tab = ctx.get("op_layers")
    if tab is None or not ctx["trace"].devices or ctx["rounds"] <= 0:
        return None
    if "layer_ms" not in ctx:
        secs = layer_seconds(ctx["trace"], *tab)
        ctx["layer_ms"] = {k: 1e3 * v / ctx["rounds"]
                           for k, v in secs.items()}
        log(f"[layers] device ms per round {ctx['layer_ms']}")
    return ctx["layer_ms"]


# -- idle by host span -----------------------------------------------------
def host_spans(tr: Trace) -> List:
    return [h for h in tr.host if h.name.startswith(PREFIX)]


def _segments(tr: Trace, spans) -> Tuple[List[float], List[str]]:
    """The window cut at every span edge: ``edges[i]..edges[i+1]`` is
    covered by the innermost span ``names[i]`` (or :data:`NONE`)."""
    inner = {t for h in spans for t in (h.start, h.end) if tr.lo < t < tr.hi}
    edges = sorted(inner | {tr.lo, tr.hi})
    names = []
    for s, e in zip(edges, edges[1:]):
        cover = [h for h in spans if h.start <= s and e <= h.end]
        names.append(min(cover, key=lambda h: h.end - h.start)
                     .name[len(PREFIX):] if cover else NONE)
    return edges, names


def idle_seconds(tr: Trace) -> Dict[str, float]:
    """Device-idle seconds in the window by the innermost ``fedsim.*``
    host span over them, averaged over the devices; the values sum to
    the window less the busy time."""
    spans = host_spans(tr)
    edges, names = _segments(tr, spans)
    out: Dict[str, float] = {}
    if not tr.devices:
        return out
    for evs in tr.devices.values():
        busy = merge([(max(e.start, tr.lo), min(e.end, tr.hi))
                      for e in evs])
        cuts = [tr.lo] + [t for iv in busy for t in iv] + [tr.hi]
        for s, e in zip(cuts[::2], cuts[1::2]):
            i = max(0, bisect.bisect_right(edges, s) - 1)
            while s < e:
                end = min(e, edges[i + 1])
                out[names[i]] = out.get(names[i], 0.0) + (end - s) * 1e-9
                s, i = end, i + 1
    return {k: v / len(tr.devices) for k, v in out.items()}


def idle_ms_per_round(ctx) -> Optional[Dict[str, float]]:
    """Idle ms per round by host span; ``None`` where the program opens
    no ``fedsim.*`` spans."""
    tr = ctx["trace"]
    if not tr.devices or ctx["rounds"] <= 0 or not host_spans(tr):
        return None
    if "idle_ms" not in ctx:
        ctx["idle_ms"] = {k: 1e3 * v / ctx["rounds"]
                          for k, v in idle_seconds(tr).items()}
        log(f"[layers] idle ms per round {ctx['idle_ms']}")
    return ctx["idle_ms"]
