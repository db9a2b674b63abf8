"""The training step by layer, from a reduced trace (``bench/tracing.py``).

The program names its layers twice. Inside the step program, named scopes
(``repro.scopes``) put each instruction under a layer: today ``forward``,
``backward``, ``ring``, ``optimizer`` or ``other``. Any other name the op
map gives is counted under its own key, so a scope the program opens
later, such as ``experts``, needs only a reader that calls ``layer_ms``.
``Engine.compiled_step_text`` gives the compiled program, whose
instruction names are the names the device trace gives its operations. On the host, ``Trainer.run`` wraps each step in a
profiler step span ``train`` holding ``trainer.batch``, ``trainer.dispatch``,
``trainer.hooks``, ``trainer.log`` and ``trainer.eval``.

Device time is counted once per instant, under the layer of the outermost
operation that covers it (a layer scan's ``while`` and its body are one
interval), and only inside the step program's module events: operations of
any other program are ``other``. The layers then add up to ``busy_s``.

Everything is per chip, averaged over the chips, and the readers divide by
the program's own step count: the ``train`` spans that hold a dispatch.
A trace without module events (the CPU backend, whose operations run on the
host's own threads) or without step spans (a program that has none) has
nothing to read.
"""
from __future__ import annotations

import bisect
import re
from typing import Dict, Optional, Tuple

import numpy as np

from bench import harness, tracing

# Reported even where no operation ran under them; other names as the op
# map gives them.
LAYERS = ("forward", "backward", "ring", "optimizer", "other")
STEP_SPAN, DISPATCH_SPAN = "train", "trainer.dispatch"
IDLE_SPANS = {"sync": "trainer.log", "feed": "trainer.batch"}


def _is_module(name: str, module: str) -> bool:
    """A module event's name is the HLO module's, with the program id in
    parentheses on a TPU."""
    return name == module or name.startswith(module + "(")


def layer_seconds(summary: tracing.Summary, op_layers: Dict[str, str],
                  module: str) -> Dict[str, float]:
    """Device seconds by layer (``LAYERS`` and every other layer that
    ``op_layers`` names), averaged over the chips. Each instant of an
    operation counts once, under the layer of the operation that started
    first among those covering it (the outermost, for nested operations);
    operations outside the ``module`` program's module events are
    ``other``."""
    names = LAYERS + tuple(sorted(set(op_layers.values()) - set(LAYERS)))
    per = []
    for d in summary.devices:
        runs = tracing._merge([(s, e) for n, s, e in d.modules
                               if _is_module(n, module)])
        starts = [s for s, _ in runs]
        tot = dict.fromkeys(names, 0)
        covered = None
        for name, s, e in sorted(d.ops, key=lambda o: (o[1], -o[2])):
            if covered is not None and e <= covered:
                continue
            lo = s if covered is None else max(s, covered)
            covered = e
            i = bisect.bisect_right(starts, s) - 1
            inside = i >= 0 and s < runs[i][1]
            tot[op_layers.get(name, "other") if inside else "other"] += e - lo
        per.append(tot)
    return {k: float(np.mean([t[k] for t in per])) / 1e9 if per else 0.0
            for k in names}


def idle_in_span_seconds(summary: tracing.Summary, span: str) -> float:
    """Device seconds in which no operation ran while a host span named
    ``span`` was open, averaged over the chips."""
    spans = [(s, e) for n, s, e in summary.host if n == span]
    per = [tracing._union_len(spans)
           - tracing._overlap_len(spans, [(s, e) for _, s, e in d.ops])
           for d in summary.devices]
    return float(np.mean(per)) / 1e9 if per else 0.0


def step_count(summary: tracing.Summary) -> int:
    """``train`` step spans that hold a ``trainer.dispatch`` span: the
    program's own count of the steps it ran."""
    dispatch = sorted(s for n, s, _ in summary.host if n == DISPATCH_SPAN)
    n = 0
    for name, s, e in summary.host:
        if name == STEP_SPAN:
            i = bisect.bisect_left(dispatch, s)
            n += i < len(dispatch) and dispatch[i] <= e
    return n


def module_name(hlo_text: str) -> str:
    """The HLO module's name from a compiled program's text."""
    m = re.match(r"HloModule ([^\s,]+)", hlo_text)
    if m is None:
        raise ValueError("not HLO text: no HloModule line")
    return m.group(1)


def _step_layers(run) -> Optional[Tuple[Dict[str, str], str]]:
    """(op layers, module name) of the cell's step program: the driver's
    engine built again for the cell and compiled for its plan's arguments
    (the program the window ran), or None where the program names no
    layers."""
    import jax
    from repro.engine import Engine
    if not hasattr(Engine, "compiled_step_text"):
        return None
    from repro import scopes
    engine, _ = harness.load_driver(run.cell).build(
        run.cell, jax.devices()[:run.chips])
    text = engine.compiled_step_text(*engine.plan().args)
    return scopes.op_layers(text), module_name(text)


def readings(run) -> Optional[dict]:
    """Per-step seconds of each layer (``layers``, where the program names
    them) and of device idle inside ``trainer.log`` and ``trainer.batch``
    (``idle``), with the step count; None where the trace has no module
    events or no step spans. Computed once per run, and kept on its
    outcome for the other readers."""
    if not hasattr(run.outcome, "layer_readings"):
        run.outcome.layer_readings = _readings(run)
    return run.outcome.layer_readings


def _readings(run) -> Optional[dict]:
    trace = run.trace
    if (trace is None or not trace.devices
            or not any(d.modules for d in trace.devices)):
        return None
    steps = step_count(trace)
    if not steps:
        return None
    out = {"steps": steps,
           "idle": {k: idle_in_span_seconds(trace, span) / steps
                    for k, span in IDLE_SPANS.items()}}
    step = _step_layers(run)
    if step is not None:
        secs = layer_seconds(trace, *step)
        out["layers"] = {k: v / steps for k, v in secs.items()}
    return out


def layer_ms(run, layer: str) -> Optional[float]:
    """Device ms per step under ``layer``; None where the trace has nothing
    to read or the program names no such layer."""
    r = readings(run)
    if r is None or layer not in r.get("layers", {}):
        return None
    return 1e3 * r["layers"][layer]


def idle_ms(run, which: str) -> Optional[float]:
    r = readings(run)
    return None if r is None else 1e3 * r["idle"][which]
