"""Device time by sublayer: one vocabulary of ``ff.*`` named scopes
inside every step program, and the map from a compiled program's
instructions back to them.

Beneath the jit boundary a profile names the Pallas kernels
(``ff_ragged_paged_c<C>`` ...) and nothing else: the rest is
``fusion.207``, a number the compiler gives and renumbers whenever the
program changes. :func:`sublayer` is a ``jax.named_scope("ff.<name>")``
from the one tuple :data:`SUBLAYERS`; the models and the engine's step
tail put every operation of a step under one. A scope is metadata: it
names the operation (``op_name=".../while/body/ff.attn.proj/dot_general"``
in the HLO, the op profile and trace viewer of any xprof capture) and
changes nothing that is computed.

An operation's sublayer is the INNERMOST ``ff.*`` component of its
``op_name`` (:func:`sublayer_of`); a fusion is one instruction, so one
event of a profile, and counts whole under its own name's (the root's,
where the fusion carries none). :func:`scope_maps` reads that off the
executables the process runs, when asked and never before.
"""
from __future__ import annotations

import contextlib
import re
import weakref
from typing import Dict, List, NamedTuple, Optional

import jax

__all__ = ["SUBLAYERS", "sublayer", "sublayer_of", "Instruction",
           "parse_instructions", "parse_scope_map", "scope_maps"]

#: The sublayers of a step, by scope name less its ``ff.`` (PERF.md
#: section 3 has what is under each and where it sits in the code).
SUBLAYERS = (
    "attn.proj",    # Q/K/V or latent projections, q/k norms, RoPE, wo
    "attn.core",    # the attention call, Pallas kernel or XLA
    "attn.write",   # the step's new lines into the page pool
    "attn.select",  # a sparse layer's block choice
    "mixer",        # a token mixer that is not attention, with its state
    "ffn",          # dense FFN, expert matmuls, shared expert
    "moe.route",    # router, grouping, weighted combine, counts
    "head",         # final norm, LM head, the sampling head
    "glue",         # embedding, block norms and residual adds, packing
)
_SCOPES = frozenset("ff." + name for name in SUBLAYERS)
# what a sublayer opens (tests/test_sublayers.py swaps in a null context
# to show that the scopes are metadata)
_named_scope = jax.named_scope


class _Sublayer(contextlib.ContextDecorator):
    """A named scope that is opened when it is entered: as a decorator
    it wraps a traced function, and each call opens a scope of its own
    (``_recreate_cm``), so one may sit at a definition for good."""

    def __init__(self, scope: str):
        self.scope = scope

    def _recreate_cm(self):
        return _Sublayer(self.scope)

    def __enter__(self):
        self._open = _named_scope(self.scope)
        return self._open.__enter__()

    def __exit__(self, *exc):
        return self._open.__exit__(*exc)


def sublayer(name: str) -> _Sublayer:
    """``jax.named_scope("ff." + name)`` for a name of :data:`SUBLAYERS`,
    as a context manager or as the decorator of a traced function; any
    other name is refused, so a typo fails where the module is imported
    or the function traced. It runs at TRACE time, never at dispatch."""
    if name not in SUBLAYERS:
        raise ValueError(
            f"no sublayer {name!r}: the vocabulary is {SUBLAYERS} "
            "(flexflow_tpu/obs/sublayers.py)")
    return _Sublayer("ff." + name)


def sublayer_of(op_name: str) -> Optional[str]:
    """The sublayer an HLO ``op_name`` lies in: its innermost (last)
    ``ff.*`` component that the vocabulary has, as ``"ff.attn.proj"``;
    None where it has none."""
    for part in reversed(op_name.split("/")):
        if part in _SCOPES:
            return part
    return None


_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+) \(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+) = ")
_SHAPE_OPCODE = re.compile(r"(\(.*?\)|\S+) ([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")


class Instruction(NamedTuple):
    """One instruction of a compiled module's text."""
    computation: Optional[str]  # the computation it is written in
    root: bool                  # that computation's ROOT
    opcode: str                 # "fusion", "custom-call", ...; "" unread
    shape: str                  # its result's, less the layout; "" unread
    op_name: str                # its metadata's; "" where it has none
    calls: Optional[str]        # the computation it calls (a fusion's body)


def parse_instructions(hlo_text: str) -> Dict[str, Instruction]:
    """``{instruction name: Instruction}`` over every instruction of a
    compiled module's text, in the text's order: the one reading of the
    text that :func:`parse_scope_map` and the tools that table a
    profile by instruction (``scripts/route_ops.py``) share."""
    out = {}
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c is not None:
                computation = c.group(1)
            continue
        what = _SHAPE_OPCODE.match(line, m.end())
        shape, opcode = what.groups() if what else ("", "")
        op = _OP_NAME.search(line)
        called = _CALLS.search(line)
        out[m.group(2)] = Instruction(
            computation, bool(m.group(1)), opcode,
            re.sub(r"\{[^}]*\}", "", shape), op.group(1) if op else "",
            called.group(1) if called else None)
    return out


def parse_scope_map(hlo_text: str) -> Dict[str, Optional[str]]:
    """``{instruction name: sublayer or None}`` over every instruction
    of a compiled module's text. An instruction with no ``ff.*`` in its
    own ``op_name`` that calls a computation (a fusion) takes its
    called computation's root's."""
    instructions = parse_instructions(hlo_text)
    own = {name: sublayer_of(i.op_name) for name, i in instructions.items()}
    roots = {i.computation: name for name, i in instructions.items() if i.root}
    calls = {name: i.calls for name, i in instructions.items()
             if own[name] is None and i.calls is not None}
    for name, called in calls.items():
        seen = set()
        while own[name] is None and called in roots and called not in seen:
            seen.add(called)
            root = roots[called]
            own[name] = own.get(root)
            called = calls.get(root)
    return own


# the engines whose step programs scope_maps() reads, oldest first;
# weak: the registry keeps none alive
_engines: List["weakref.ref"] = []


def register(engine) -> None:
    """``InferenceEngine.__init__`` adds itself."""
    _engines[:] = [ref for ref in _engines if ref() is not None]
    _engines.append(weakref.ref(engine))


def live_engines() -> list:
    return [e for e in (ref() for ref in _engines) if e is not None]


def scope_maps(engines=None, programs=None
               ) -> Dict[str, Dict[str, Optional[str]]]:
    """``{program name as a profile's XLA Modules line shows it
    ("jit_ff_step_c128_t512"): {HLO instruction name: sublayer or
    None}}`` for every step program that ``engines`` (default: the
    process's live engines, oldest first) have compiled through
    ``InferenceEngine._jit``, or those of them that ``programs`` names,
    parsed from the compiled executables' own text
    (``InferenceEngine.step_program_texts``: each program is lowered
    again with the abstract arguments it was traced with, which is
    seconds a program, so ask after the measured work). Asking counts
    as no trace, compile or dispatch. Two engines' programs of one
    name: the newer engine's map stands. A reader joins the map to a
    profile's events by program and instruction name; a name the map
    lacks says the executable that ran was another."""
    names = None if programs is None else {
        p[len("jit_"):] for p in programs if p.startswith("jit_")}
    maps: Dict[str, Dict[str, Optional[str]]] = {}
    for engine in live_engines() if engines is None else engines:
        for name, text in engine.step_program_texts(names).items():
            maps["jit_" + name] = parse_scope_map(text)
    return maps
