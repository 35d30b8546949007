#!/usr/bin/env python
"""What XLA computes more than once: the instructions its rematerialisation
pass cloned (``.remat``, ``.remat2``, ``.remat.1``, ...) in a program of this
repository.

    python tools/xla_clones.py chiprun_out/byop60_3160000001.json
    python tools/xla_clones.py round.hlo.txt[.gz] [--program NAME]

Reads a by-operation table of a traced run (``{program: {count, total_us,
ops_us: {instruction text: us}}}``, summed inside each program run) or the
text of ONE compiled program (``compiled.as_text()``), and lists a program's
clones by output shape and by the parameter they read: how many, the
milliseconds a program run they cost (a table alone has times) and whether
they hold a product.  The clones of one instruction share its name; where
one of them reads a prefetched copy of a parameter (``%custom-call.N``), a
sibling names the parameter for it.

A product cut for several consumers may be computed once a consumer (PR 61:
Mamba-2's ``in_proj`` four times a layer in the 52-layer round, in no
shallower one): read the DEEPEST program's table when a roofline is written.
No code a cell runs.
"""

from __future__ import annotations

import argparse
import gzip
import json
import re
import sys
from typing import Dict, Iterable, List, NamedTuple, Optional, Tuple

# ``.remat``, ``.remat4`` and, made unique another way, ``.remat.1``
_CLONE = re.compile(r"\.remat\d*(?:\.\d+)?$")
_HEAD = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+) = (.*)$")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
# ``bf16[544,2688] %name`` (a trace's event) or ``%name`` (a compiled text)
_OPERAND = re.compile(r"(?:([a-z]+\d*[a-z0-9]*\[[\d,]*\]) )?%([\w.\-]+)")
_CALLS = re.compile(r"calls=(%?[\w.\-]+)")
_PRODUCTS = ("convolution(", "dot(")


class Instruction(NamedTuple):
    name: str                           # without the leading %
    shape: str                          # ``bf16[544,10304]``; a tuple's first
    opcode: str
    operands: Tuple[Tuple[str, str], ...]   # (shape or "", name) as far as
    #                                         the text goes (a table cuts it)
    calls: Optional[str]


def parse(text: str) -> Optional[Instruction]:
    """One line of HLO text (or a table's key, cut at 200 characters)."""
    head = _HEAD.match(text)
    if head is None:
        return None
    name = head.group(1).lstrip("%")
    # without layouts (``{1,0:T(8,128)(2,1)S(1)}``), whose tiles read as calls
    rest = re.sub(r"\{[^{}]*\}", "", head.group(2))
    shape = _ARRAY.search(rest)
    # the opcode is the word before the parenthesis that opens the operands:
    # behind the result's shape (a tuple's: behind its closing parenthesis)
    depth, end = 0, 0
    if rest.startswith("("):
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
    call = re.search(r"([\w\-]+)\(", rest[end:])
    if shape is None or call is None:
        return None
    body = rest[end + call.end():]
    calls = _CALLS.search(body)
    return Instruction(
        name, f"{shape.group(1)}[{shape.group(2)}]", call.group(1),
        tuple(_OPERAND.findall(body.split("), ")[0])),
        calls.group(1).lstrip("%") if calls else None)


def base_of(name: str) -> Optional[str]:
    """The instruction a clone repeats; None for one that is no clone."""
    return _CLONE.sub("", name) if _CLONE.search(name) else None


def _dims(shape: str) -> Tuple[int, ...]:
    return tuple(int(d) for d in shape[shape.index("[") + 1:-1].split(",")
                 if d)


def product_by_shapes(inst: Instruction) -> bool:
    """``(m, n)`` out of an ``(m, k)`` and a ``(k, n)`` or ``(n, k)``
    operand (dimensions of 1 aside): what a table, which holds no fused
    computation, can tell."""
    def two(shape):
        dims = tuple(d for d in _dims(shape) if d != 1)
        return dims if len(dims) == 2 else None
    out = two(inst.shape)
    if out is None:
        return False
    m, n = out
    ops = [d for d in (two(s) for s, _ in inst.operands if s) if d]
    return any(a[0] == m and b in ((a[1], n), (n, a[1])) and a != b
               for a in ops for b in ops)


def generalise(parameter: str) -> str:
    """``params__layer46____in_proj__.1`` -> ``params__layerN____in_proj__``:
    one line for the layers of a model."""
    return re.sub(r"layer\d+", "layerN", re.sub(r"\.\d+$", "", parameter))


class Program:
    """The instructions of one program: ``insts`` with the microseconds of
    each over ``runs`` program runs (None from a compiled text), the names of
    its parameters and, from a text, which computations hold a product."""

    def __init__(self, name: str, runs: Optional[int] = None):
        self.name, self.runs = name, runs
        self.insts: List[Tuple[Instruction, Optional[float]]] = []
        self.parameters: Optional[set] = None   # None: a table, see below
        self.product_computations: Optional[set] = None

    @classmethod
    def from_table(cls, name: str, record: dict) -> "Program":
        self = cls(name, int(record["count"]))
        for text, us in record["ops_us"].items():
            inst = parse(text)
            if inst is not None:
                self.insts.append((inst, float(us)))
        return self

    @classmethod
    def from_text(cls, name: str, lines: Iterable[str]) -> "Program":
        self = cls(name)
        self.parameters, self.product_computations = set(), set()
        computation = None
        for line in lines:
            if line.rstrip().endswith("{") and " = " not in line.split("(")[0]:
                words = line.split()
                computation = words[1 if words[0] == "ENTRY" else 0].lstrip(
                    "%")
                continue
            inst = parse(line)
            if inst is None:
                continue
            if inst.opcode == "parameter":
                self.parameters.add(inst.name)
            if any(p in line for p in _PRODUCTS) and computation:
                self.product_computations.add(computation)
            if computation is None or not computation.startswith("fused_"):
                self.insts.append((inst, None))
        return self

    def is_parameter(self, name: str, executed: set) -> bool:
        if self.parameters is not None:
            return name in self.parameters
        # a table holds what RAN: a parameter never does, and neither do
        # the compiler's own unexecuted names, which are made of opcodes
        stem = name.split(".")[0]
        return name not in executed and not re.fullmatch(
            r"[a-z\-]+(_[a-z\-]+)*", stem)

    def holds_product(self, inst: Instruction) -> bool:
        if inst.opcode in ("convolution", "dot"):
            return True
        if self.product_computations is not None and inst.calls:
            return inst.calls in self.product_computations
        return product_by_shapes(inst)

    def clones(self) -> List[dict]:
        """One entry a (shape, parameter): ``count``, ``ms`` a program run,
        ``product``, the clones' ``names`` in the program's order."""
        executed = {inst.name for inst, _ in self.insts}
        read = {}               # base name -> the parameter a sibling reads
        for inst, _us in self.insts:
            base = base_of(inst.name)
            if base is None:
                continue
            for _shape, operand in inst.operands:
                if self.is_parameter(operand, executed):
                    read.setdefault(base, operand)
        groups: Dict[tuple, dict] = {}
        for inst, us in self.insts:
            base = base_of(inst.name)
            if base is None:
                continue
            parameter = read.get(base)
            key = (inst.shape, generalise(parameter) if parameter else None)
            g = groups.setdefault(key, {
                "shape": inst.shape, "parameter": key[1], "count": 0,
                "ms": None if us is None else 0.0, "product": False,
                "names": [], "reads": []})
            g["count"] += 1
            if us is not None:
                g["ms"] += us / self.runs / 1e3
            g["product"] |= self.holds_product(inst)
            g["names"].append(inst.name)
            g["reads"].append(parameter)
        return sorted(groups.values(),
                      key=lambda g: (-(g["ms"] or 0.0), -g["count"]))


def load(path: str, program: Optional[str] = None) -> List[Program]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt", encoding="utf-8") as f:
        head = f.read(1)
        f.seek(0)
        if head == "{":
            table = json.load(f)
            return [Program.from_table(name, rec)
                    for name, rec in table.items()
                    if program in (None, name)]
        return [Program.from_text(program or path, f)]


def report(programs: List[Program], top: int = 12) -> str:
    out = []
    for prog in programs:
        groups = prog.clones()
        n = sum(g["count"] for g in groups)
        timed = prog.runs is not None
        total = sum(g["ms"] for g in groups) if timed else None
        out.append(f"== {prog.name}: {n} cloned instructions"
                   + (f", {total:.2f} ms a run over {prog.runs} runs"
                      if timed else " (compiled text: no times)"))
        for g in groups[:top]:
            ms = f"{g['ms']:8.3f} ms" if timed else ""
            out.append(f"  x{g['count']:<4d}{ms}  {g['shape']:28s} "
                       f"{'PRODUCT' if g['product'] else 'no product':10s} "
                       f"reads {g['parameter'] or '-'}")
            out.append(f"        e.g. {', '.join(g['names'][:4])}")
        if len(groups) > top:
            out.append(f"  ... and {len(groups) - top} more shapes")
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("path", help="a by-operation table (.json) or a "
                    "compiled program's text (.txt, .gz)")
    ap.add_argument("--program", help="a table: this program alone; a "
                    "text: the name to print")
    ap.add_argument("--top", type=int, default=12)
    args = ap.parse_args(argv)
    print(report(load(args.path, args.program), args.top))
    return 0


if __name__ == "__main__":
    sys.exit(main())
