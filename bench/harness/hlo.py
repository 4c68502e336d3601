"""Kernel calls and their operand shapes, read from compiled HLO text.

Each Mosaic kernel is one ``custom-call`` with ``custom_call_target=
"tpu_custom_call"``; its instruction is named after the jitted kernel
wrapper (``lns_qmatmul_pallas.1``) and the device trace names its events
the same way. Operands that the wrapper padded to tile multiples come from
a ``pad`` (or a fusion whose root is one): the real shape is the pad's
input, so padding shows as work the kernel did not need to do.
"""
from __future__ import annotations

import re
from typing import Dict, Optional, Tuple

_INSTR = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(([^()]*)\)")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPNAME = re.compile(r'op_name="([^"]*)"')
_COMP = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")

Shape = Tuple[str, Tuple[int, ...]]


def _shape(text: str) -> Optional[Shape]:
    m = _SHAPE.search(text)
    if not m:
        return None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return m.group(1), dims


def module_name(hlo_text: str) -> str:
    first = hlo_text.lstrip().split("\n", 1)[0]
    m = re.match(r"HloModule\s+([\w.\-]+)", first)
    return m.group(1) if m else ""


def parse(hlo_text: str) -> Dict[str, dict]:
    """Every instruction: opcode, first result shape, operand names,
    called computation, and the computation it sits in; plus each
    computation's root under ``"<comp>:ROOT"``."""
    instrs: Dict[str, dict] = {}
    comp = None
    for line in hlo_text.splitlines():
        cm = _COMP.match(line)
        if cm and "=" not in line.split("{")[0]:
            comp = cm.group(2)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.group(1), m.group(2)
        om = _OPCODE.search(rest)
        if not om:
            continue
        type_text = rest[:om.start()]
        operands = [o.strip().lstrip("%").split(" ")[-1].lstrip("%")
                    for o in om.group(2).split(",") if o.strip()]
        calls = _CALLS.search(rest)
        opname = _OPNAME.search(rest)
        rec = {"opcode": om.group(1), "shape": _shape(type_text),
               "operands": operands,
               "calls": calls.group(1) if calls else None, "comp": comp,
               "op_name": opname.group(1) if opname else "",
               "custom": 'custom_call_target="tpu_custom_call"' in rest}
        instrs[name] = rec
        if line.lstrip().startswith("ROOT"):
            instrs[f"{comp}:ROOT"] = rec
    return instrs


def _real_shape(instrs: Dict[str, dict], name: str) -> Optional[Shape]:
    rec = instrs.get(name)
    if rec is None:
        return None
    for _ in range(3):
        if rec["opcode"] in ("bitcast", "copy", "reshape") and rec["operands"]:
            nxt = instrs.get(rec["operands"][0])
            if nxt is None:
                break
            rec = nxt
            continue
        break
    if rec["opcode"] == "pad" and rec["operands"]:
        src = instrs.get(rec["operands"][0])
        if src is not None and src["shape"] is not None:
            return src["shape"]
    if rec["opcode"] == "fusion" and rec["calls"]:
        root = instrs.get(f"{rec['calls']}:ROOT")
        if root is not None and root["opcode"] == "pad" and root["operands"]:
            src = instrs.get(root["operands"][0])
            if src is not None and src["shape"] is not None:
                return src["shape"]
    return instrs[name]["shape"]


def kernel_of(name: str, rec: dict) -> str:
    """The kernel wrapper a custom call came from: the innermost
    ``jit(<wrapper>)`` before ``pallas_call`` in its op name, else the
    instruction name without its numeric suffix."""
    hits = re.findall(r"jit\(([\w.\-]+)\)/pallas_call", rec.get("op_name", ""))
    if hits:
        return hits[-1]
    return re.sub(r"\.\d+$", "", name)


def kernel_calls(hlo_text: str) -> Dict[str, dict]:
    """``{instruction name: {"kernel", "operands": [(dtype, real,
    padded)]}}`` for every Mosaic kernel call."""
    instrs = parse(hlo_text)
    out = {}
    for name, rec in instrs.items():
        if ":" in name or not rec["custom"]:
            continue
        ops = []
        for o in rec["operands"]:
            padded = instrs[o]["shape"] if o in instrs else None
            real = _real_shape(instrs, o)
            if padded is None or real is None:
                continue
            ops.append((padded[0], real[1], padded[1]))
        out[name] = {"kernel": kernel_of(name, rec), "operands": ops}
    return out


def jaxpr_real_shapes(closed) -> Dict[tuple, tuple]:
    """``{(kernel, padded operand shapes): real operand shapes}`` from a
    traced program: a kernel wrapper's operand that a ``pad`` made has the
    pad's input as its real shape. XLA may turn such pads into masked
    selects that HLO text no longer shows; the program as traced still
    does."""
    out: Dict[tuple, tuple] = {}

    def sub_jaxprs(params):
        for v in params.values():
            for x in (v if isinstance(v, (list, tuple)) else (v,)):
                j = getattr(x, "jaxpr", None)
                if j is not None and hasattr(j, "eqns"):
                    yield j
                elif hasattr(x, "eqns"):
                    yield x

    def walk(jaxpr):
        made = {}
        for eqn in jaxpr.eqns:
            for o in eqn.outvars:
                made[o] = eqn
            name = eqn.params.get("name", "") if eqn.primitive.name in (
                "pjit", "jit") else ""
            if name.endswith("_pallas"):
                padded, real = [], []
                for v in eqn.invars:
                    shape = tuple(getattr(v.aval, "shape", ()))
                    src = made.get(v)
                    if src is not None and (src.primitive.name == "pad" or (
                            src.primitive.name in ("pjit", "jit")
                            and src.params.get("name") == "_pad")):
                        rshape = tuple(src.invars[0].aval.shape)
                    else:
                        rshape = shape
                    padded.append(shape)
                    real.append(rshape)
                keep = [i for i, s in enumerate(padded)
                        if len(s) >= 2 and s != (1, 1)]
                out[(name, tuple(padded[i] for i in keep))] = tuple(
                    real[i] for i in keep)
            for j in sub_jaxprs(eqn.params):
                walk(j)

    walk(closed.jaxpr)
    return out


def apply_real_shapes(calls: Dict[str, dict], real: Dict[tuple, tuple]
                      ) -> Dict[str, dict]:
    """Replace each call's real operand shapes by the traced program's
    where the padded shapes match."""
    for call in calls.values():
        ops = [o for o in call["operands"]
               if len(o[2]) >= 2 and tuple(o[2]) != (1, 1)]
        key = (call["kernel"], tuple(tuple(o[2]) for o in ops))
        if key in real:
            call["real"] = [tuple(s) for s in real[key]]
        else:
            call["real"] = [tuple(o[1]) for o in ops]
    return calls
