"""The training step's layers, named inside the step program.

The step functions (``core/stale_sync.py``, ``core/staleness.py``,
``optim/optimizers.py``) open ``jax.named_scope`` with one of three names:

* ``MODEL``: the ``jax.value_and_grad`` of the loss. Ops under a
  ``transpose(...)`` scope below it are the backward pass (remat's
  recomputation runs there, so it counts as backward); the rest is the
  forward pass.
* ``RING``: delivery. The step's key split and delay draw, the ring write
  and read, the worker mean, and on the packed route the packing and
  ``stale_accum``.
* ``OPTIMIZER``: the update. ``optimizer.update`` or the packed Adam pass,
  the staleness-aware LR scale, the parameter add and the step's metrics.

XLA keeps the scope path in each instruction's ``op_name`` metadata, so a
compiled program says which layer every instruction belongs to
(:func:`op_layers`), and a device trace, which names operations by
instruction, can be read by layer. Scopes are metadata only: the compiled
instructions are the same with and without them.
"""
from __future__ import annotations

import re
from typing import Dict

MODEL, RING, OPTIMIZER = "model", "ring", "optimizer"
LAYERS = ("forward", "backward", "ring", "optimizer", "other")

# A path component naming a scope, bare or wrapped by a transformation:
# "model", "vmap(model)", "transpose(jvp(model))".
_SCOPE = re.compile(r"^(?:[\w.]+\()*(%s|%s|%s)\)*$" % (MODEL, RING,
                                                        OPTIMIZER))
_INSTR = re.compile(r"^\s*(ROOT\s+)?%?([^\s=]+) = (.*)$")
_HEADER = re.compile(r"^(?:ENTRY\s+)?%?([^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([^\s,]+)")
_REF = re.compile(r"%([^\s,(){}=]+)")


def layer_of(op_name: str) -> str:
    """The layer of an instruction from its ``op_name`` path. The innermost
    scope decides, so a scope opened inside another one names its own ops.
    Where XLA merged several paths (``;``), the first one counts."""
    path = op_name.split(";", 1)[0].split("/")
    for i in range(len(path) - 1, -1, -1):
        m = _SCOPE.match(path[i])
        if m is None:
            continue
        if m.group(1) != MODEL:
            return m.group(1)
        return ("backward" if any("transpose(" in part for part in path[i:])
                else "forward")
    return "other"


def op_layers(hlo_text: str) -> Dict[str, str]:
    """Every instruction of a compiled program's text (nested computations
    included) mapped to its layer.

    XLA makes some instructions without the metadata of what they compute:
    a fusion whose own ``op_name`` names no scope takes the layer of the
    computation it fuses (its root's, else its first scoped instruction's),
    and any other instruction without a scope, such as a copy or a
    broadcast of zeros the compiler added, takes the layer of its first
    user in program order, else that of the instruction that runs its
    computation (a ``while`` for its body). What is left is ``other``."""
    comps = {}                   # computation -> [[name, layer, refs, calls]]
    roots = {}
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            h = _HEADER.match(line)
            if h is not None:
                comp = h.group(1)
                comps[comp] = []
            continue
        if comp is None:
            continue
        rest = m.group(3)
        name = _OP_NAME.search(rest)
        layer = layer_of(name.group(1)) if name else "other"
        calls = _CALLS.search(rest)
        ins = [m.group(2), None if layer == "other" else layer,
               set(_REF.findall(rest)), calls.group(1) if calls else None]
        comps[comp].append(ins)
        if m.group(1):
            roots[comp] = ins
    done, comp_layer = set(), {}

    def resolve(comp):
        if comp in done:
            return
        done.add(comp)
        body = comps[comp]
        for ins in body:
            if ins[1] is None and ins[3] in comps:
                resolve(ins[3])
                ins[1] = comp_layer[ins[3]]
        users = {}
        for ins in body:
            for ref in ins[2]:
                users.setdefault(ref, []).append(ins)
        for ins in reversed(body):
            if ins[1] is None:
                ins[1] = next((u[1] for u in users.get(ins[0], ())
                               if u[1] is not None), None)
        root = roots.get(comp)
        comp_layer[comp] = (root[1] if root is not None and root[1]
                            else next((ins[1] for ins in body if ins[1]),
                                      None))

    for comp in comps:
        resolve(comp)
    # Left over (loop-carried copies in a while body, say): the layer of the
    # instruction that runs the computation.
    callers = {ref: ins for body in comps.values() for ins in body
               for ref in ins[2] if ref in comps}
    changed = True
    while changed:
        changed = False
        for comp, body in comps.items():
            layer = callers[comp][1] if comp in callers else None
            for ins in body:
                if ins[1] is None and layer is not None:
                    ins[1], changed = layer, True
    return {ins[0]: ins[1] or "other"
            for body in comps.values() for ins in body}
