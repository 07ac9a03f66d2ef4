"""CompileGuard: ``jax.jit`` with an enforced compile-count contract.

The serve engine's recompile-free contract (DESIGN.md §12) was a
single-site assertion — ``decode_cache_size == 1`` read off the jitted
decode step after the fact.  CompileGuard generalizes it to every
compiled path in the system: each entry point declares up front how
many distinct programs it is allowed to compile (``max_programs``, the
round paths all declare 1), the wrapper records the abstract signature
of every call, and a call that would cross the budget raises
:class:`CompileGuardError` **before** paying for the retrace — naming
the argument whose shape/dtype/structure changed, which is exactly the
information a silent recompile hides.

Donation rides the same wrapper: ``donate_argnums`` is forwarded to
``jax.jit`` and kept introspectable (``guard.donate_argnums``) so the
static analyzer (``repro.analysis.tracecheck``) can assert the round
paths donate their dead params/accumulator buffers and that the
lowering actually aliased them (no silent copies).

The guard also tells which stage of the round each operation of its
compiled programs belongs to (``stage_maps``): the round code wraps its
stages in ``jax.named_scope("fl/<stage>")``, and the compiled HLO keeps
the scope in each instruction's ``op_name`` metadata.  A profiler trace
of the chip names operations by HLO instruction and program, not by
scope, so this map is what ties a device operation to its stage.

This module must stay import-light (jax only): ``core/`` and ``serve/``
import it, so it cannot import anything from ``repro``.
"""
from __future__ import annotations

import re
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax

__all__ = ["CompileGuard", "CompileGuardError", "hlo_stages"]

# an HLO instruction line: ``[ROOT ]%name = <type> <opcode>(<operands>)...``
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation|branch_computations)="
                    r"(\{[^}]*\}|%?[\w.\-]+)")
_REF = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
# a round stage's scope in an op_name, also inside a transform: vmap(fl/x)
_SCOPE = re.compile(r"(?:^|[/(])fl/([\w\-]+)")


def _operands(rest: str) -> List[str]:
    """The operand names of an instruction from what follows ``= ``:
    skip the result type (which nests brackets: a tuple, a tiled layout
    ``{1,0:T(8,128)}``), then read the opcode's parenthesised operands."""
    depth, i = 0, len(rest)
    for k, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            i = k
            break
    i = rest.find("(", i)
    if i < 0:
        return []
    depth = 0
    for j in range(i, len(rest)):
        depth += {"(": 1, ")": -1}.get(rest[j], 0)
        if depth == 0:
            return _REF.findall(rest[i:j])
    return _REF.findall(rest[i:])


def hlo_stages(text: str, inherit: bool = True
               ) -> Tuple[str, Dict[str, str]]:
    """``(module name, {instruction name: stage})`` from an HLO module's
    text.  An instruction's stage is the innermost ``fl/<stage>`` scope
    in its ``op_name`` metadata.  With ``inherit``, an instruction
    the compiler added without metadata (a copy, the done half of an
    async copy, a layout change) takes, until nothing changes, the stage
    of the instruction that calls its computation (a while loop's body,
    a fusion), else of its first user with one, else of its first
    operand with one.  Instructions with no stage are left out."""
    m = _MODULE.search(text)
    stages: Dict[str, str] = {}
    comp_of: Dict[str, str] = {}                 # instruction -> computation
    operands: Dict[str, List[str]] = {}
    callees: Dict[str, List[str]] = {}            # instruction -> computations
    comp = ""
    for line in text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(1)
            continue
        i = _INSTR.match(line)
        if not i:
            continue
        name, rest = i.groups()
        comp_of[name] = comp
        op = _OP_NAME.search(rest)
        found = _SCOPE.findall(op.group(1)) if op else []
        if found:
            stages[name] = found[-1]
        if inherit:
            operands[name] = _operands(rest)
            callees[name] = [r for g in _CALLS.findall(rest)
                             for r in (_REF.findall(g) or [g])]
    if inherit:
        members: Dict[str, List[str]] = {}
        users: Dict[str, List[str]] = {}
        for name, ops in operands.items():
            members.setdefault(comp_of[name], []).append(name)
            for o in ops:
                users.setdefault(o, []).append(name)

        def from_callers() -> bool:
            found = False
            for name, comps in callees.items():
                st = stages.get(name)
                for k in comps if st else ():
                    for inner in members.get(k, ()):
                        if inner not in stages:
                            stages[inner] = st
                            found = True
            return found

        def sweep(near) -> bool:
            found, again = False, True
            while again:
                again = False
                for name in operands:
                    if name not in stages:
                        st = [stages[n] for n in near(name) if n in stages]
                        if st:
                            stages[name] = st[0]
                            found = again = True
            return found

        # callers first, then users, and operands only where neither
        # gives a stage
        while (from_callers() | sweep(lambda n: users.get(n, []))
               or sweep(lambda n: operands[n])):
            pass
    return (m.group(1) if m else ""), stages


def _abstract(x):
    """What lowering needs of one argument leaf: its shape, dtype, weak
    type and (for a committed array) its sharding."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        # a tracer (the guard called under eval_shape) has no placement
        committed = not isinstance(x, jax.core.Tracer) \
            and getattr(x, "committed", False)
        sharding = x.sharding if committed else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding,
                                    weak_type=getattr(x, "weak_type", False))
    return x


class CompileGuardError(RuntimeError):
    """A guarded entry point tried to compile more programs than its
    declared budget (or retraced without a visible signature change)."""


def _leaf_spec(x) -> Tuple:
    """Hashable abstract spec of one argument leaf (what jit keys on)."""
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return ("array", tuple(x.shape), str(x.dtype),
                bool(getattr(x, "weak_type", False)))
    # python scalars trace as weak-typed 0-d arrays: the value never
    # forces a retrace, only the python type can
    return ("py", type(x).__name__)


def _spec_str(spec: Tuple) -> str:
    if spec[0] == "array":
        kind, shape, dtype, weak = spec
        return f"{dtype}{list(shape)}" + ("*" if weak else "")
    return f"<{spec[1]}>"


class CompileGuard:
    """Wrap ``fn`` in ``jax.jit`` and enforce a program-count budget.

    Parameters
    ----------
    fn:            the python function to jit (kept on ``guard.fn``).
    name:          label used in error messages and analyzer reports.
    max_programs:  how many distinct compiled programs this entry point
                   may own; ``None`` = unbounded (signature history is
                   still recorded for reporting).  The round paths and
                   the serve decode step declare 1; serve prefill is
                   unbounded (one program per distinct prompt length is
                   the documented shape cache).
    donate_argnums: forwarded to ``jax.jit`` and kept introspectable.
    jit_kwargs:    any further ``jax.jit`` options (``in_shardings``,
                   ``out_shardings``, ``static_argnums``, ...).
    """

    def __init__(self, fn: Callable, *, name: Optional[str] = None,
                 max_programs: Optional[int] = 1,
                 donate_argnums: Sequence[int] = (),
                 **jit_kwargs: Any):
        self.fn = fn
        self.name = name or getattr(fn, "__name__", "jitted")
        self.max_programs = max_programs
        self.donate_argnums = tuple(donate_argnums)
        self._jit = jax.jit(fn, donate_argnums=self.donate_argnums,
                            **jit_kwargs)
        # call-order history of abstract signatures:
        # sig key -> [(pretty arg path, leaf spec), ...]
        self._sigs: Dict[Tuple, List[Tuple[str, Tuple]]] = {}
        # sig key -> the call's (args, kwargs) as ShapeDtypeStructs
        self._abstract: Dict[Tuple, Tuple] = {}

    # -- signature bookkeeping ----------------------------------------------

    def _signature(self, args, kwargs):
        leaves, treedef = jax.tree_util.tree_flatten((args, dict(kwargs)))
        paths = [jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path((args,
                                                       dict(kwargs)))[0]]
        specs = tuple(_leaf_spec(x) for x in leaves)
        key = (str(treedef), specs)
        pretty = list(zip(paths, specs))
        return key, pretty

    def _diff(self, pretty) -> List[str]:
        """Human diff of the new signature vs the last recorded one."""
        if not self._sigs:
            return []
        old = list(self._sigs.values())[-1]
        if len(old) != len(pretty):
            return [f"argument structure changed: {len(old)} -> "
                    f"{len(pretty)} leaves (e.g. an optional argument "
                    f"appeared or a pytree changed shape)"]
        out = []
        for (op, os), (np_, ns) in zip(old, pretty):
            if os != ns or op != np_:
                out.append(f"arg {np_ or op}: "
                           f"{_spec_str(os)} -> {_spec_str(ns)}")
        return out or ["no shape/dtype change visible — weak_type or "
                       "sharding drift forced the retrace"]

    def _record(self, args, kwargs, *, about_to_compile: bool):
        key, pretty = self._signature(args, kwargs)
        if key in self._sigs:
            return
        if (about_to_compile and self.max_programs is not None
                and len(self._sigs) >= self.max_programs):
            diff = "\n  ".join(self._diff(pretty))
            raise CompileGuardError(
                f"CompileGuard[{self.name}]: call would compile program "
                f"#{len(self._sigs) + 1} (budget {self.max_programs}). "
                f"Retrace-triggering argument(s):\n  {diff}")
        self._sigs[key] = pretty
        self._abstract[key] = jax.tree_util.tree_map(_abstract,
                                                     (args, dict(kwargs)))

    # -- jit surface --------------------------------------------------------

    def __call__(self, *args, **kwargs):
        self._record(args, kwargs, about_to_compile=True)
        out = self._jit(*args, **kwargs)
        # ground truth: jit may retrace on distinctions our spec does
        # not model (e.g. sharding changes); catch those after the fact
        n = self.cache_size
        if self.max_programs is not None and n > self.max_programs:
            raise CompileGuardError(
                f"CompileGuard[{self.name}]: jit cache holds {n} "
                f"programs (budget {self.max_programs}) but the call "
                f"signatures look identical — a non-shape retrace "
                f"(sharding/weak_type) slipped through")
        return out

    def lower(self, *args, **kwargs):
        """Explicit lowering (dry-run paths); counts against the budget."""
        self._record(args, kwargs, about_to_compile=True)
        return self._jit.lower(*args, **kwargs)

    def eval_shape(self, *args, **kwargs):
        return jax.eval_shape(self.fn, *args, **kwargs)

    # -- introspection (used by repro.analysis.tracecheck) ------------------

    @property
    def cache_size(self) -> int:
        """Number of compiled programs: max of the jit cache (executed
        calls) and the recorded signature count (``lower()`` calls)."""
        try:
            cached = self._jit._cache_size()
        except Exception:
            cached = 0
        return max(cached, len(self._sigs))

    @property
    def programs(self) -> List[List[Tuple[str, str]]]:
        """Recorded signatures, call order: [[(arg path, spec), ...]]."""
        return [[(p, _spec_str(s)) for p, s in sig]
                for sig in self._sigs.values()]

    def stage_maps(self, inherit: bool = True) -> Dict[str, Dict[str, str]]:
        """For each program this guard compiled: its HLO module name ->
        ``{instruction name: stage}`` (``hlo_stages`` of the optimised
        HLO).  Lowers and compiles each recorded signature again, which
        the compilation caches serve where they hold the program."""
        out = {}
        for args, kwargs in self._abstract.values():
            text = self._jit.lower(*args, **kwargs).compile().as_text()
            name, stages = hlo_stages(text, inherit)
            out[name] = stages
        return out

    def assert_programs(self, n: int):
        """Hard assertion for smoke gates: at most ``n`` programs."""
        if self.cache_size > n:
            raise CompileGuardError(
                f"CompileGuard[{self.name}]: {self.cache_size} compiled "
                f"programs, expected <= {n}")
