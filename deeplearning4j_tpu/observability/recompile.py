"""Recompile detection for jitted step functions.

On TPU the silent throughput killer is XLA retracing/recompilation from
shape churn — a ragged final batch, a TBPTT tail window, a mask appearing
mid-run — each costing seconds of compile against a millisecond step.
Nothing in the reference detects this (it has no compiler in the loop).

``instrument(jax.jit(step), "name")`` wraps the jitted callable: every call
fingerprints the *abstract* signature of the inputs (pytree structure +
shape/dtype/sharding per leaf — the things jit keys its cache on), counts
distinct signatures as compiles in the metrics registry, and logs ONE
warning per *new* signature after the first with the old→new delta, e.g.::

    recompile #2 of MultiLayerNetwork.train_step: args[4]:
    f32[128,784] -> f32[96,784]

The fingerprint is a few microseconds of host work per call (tuple of
shape/dtype ids per leaf); the paths needed for a readable delta are only
computed on a miss.

The same miss path is where a program remembers how to describe itself:
``register_program`` keeps the program traced at the abstract signature it
was called with, and ``program_scopes(name)`` compiles it again from that on
demand and returns, per instruction of the compiled module, the
``jax.named_scope`` path its device operation lies under (docs/
observability.md, "Device time by layer").
"""

from __future__ import annotations

import contextlib
import logging
import re
import threading
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

from deeplearning4j_tpu.observability import profiling, shardstats

logger = logging.getLogger("deeplearning4j_tpu.observability")

_COMPILES = "dl4j_compiles_total"
_RECOMPILES = "dl4j_recompiles_total"


def _leaf_sig(leaf: Any) -> Tuple:
    """Abstract signature of one pytree leaf: what jit keys its cache on.
    The sharding is kept as the OBJECT (hashable, cheap) — stringifying it
    per call was the dominant fingerprint cost on large pytrees."""
    shape = getattr(leaf, "shape", None)
    if shape is None:
        # non-array static-ish leaf (python scalar, string…): jit treats
        # python numbers as weak-typed 0-d arrays; keep the type
        return (type(leaf).__name__,)
    dtype = getattr(leaf, "dtype", None)
    return (tuple(shape), str(dtype), getattr(leaf, "sharding", None))


def _fmt_leaf_sig(sig: Tuple) -> str:
    if len(sig) == 1:
        return sig[0]
    shape, dtype, sharding = sig
    short = {"float32": "f32", "float64": "f64", "bfloat16": "bf16",
             "int32": "i32", "int64": "i64", "bool": "b1",
             "uint32": "u32"}.get(dtype, dtype)
    s = f"{short}[{','.join(str(d) for d in shape)}]"
    sh = "" if sharding is None else repr(sharding)
    if sh and "SingleDevice" not in sh:
        s += f"@{sh}"
    return s


def fingerprint(args: Tuple, kwargs: Dict) -> Tuple:
    """Hashable abstract signature of a call's inputs."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten((args, kwargs))
    return (treedef, tuple(_leaf_sig(l) for l in leaves))


def _fmt_signature(sig: Tuple, max_leaves: int = 12) -> str:
    """Readable one-line form of a fingerprint's leaf signatures
    (``f32[128,784], f32[128,10], …``) for flight-recorder records."""
    _treedef, leaves = sig
    parts = [_fmt_leaf_sig(s) for s in leaves[:max_leaves]]
    if len(leaves) > max_leaves:
        parts.append(f"… {len(leaves) - max_leaves} more")
    return ", ".join(parts)


def _leaf_paths(args: Tuple, kwargs: Dict) -> List[str]:
    """Human-readable path per leaf, same order as ``fingerprint``."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path((args, kwargs))[0]
    out = []
    for path, _ in flat:
        label = jax.tree_util.keystr(path)
        # keystr renders "(0,)[4]['w']" style; trim the (args, kwargs) root
        if label.startswith("[0]"):
            label = "args" + label[3:]
        elif label.startswith("[1]"):
            label = "kwargs" + label[3:]
        out.append(label)
    return out


class RecompileDetector:
    """Tracks abstract input signatures of one jitted function."""

    def __init__(self, name: str, registry=None,
                 warn: Optional[Callable[[str], None]] = None):
        from deeplearning4j_tpu.observability.metrics import get_registry

        self.name = name
        self.warn = warn or logger.warning
        self._lock = threading.Lock()
        self._seen: Dict[Tuple, int] = {}   # signature -> compile ordinal
        self._last: Optional[Tuple] = None
        self.compile_count = 0
        self.recompile_count = 0  # new signatures after the first
        # signature -> XLA cost analysis (filled when a profiler is
        # installed; see check(cost_fn=)).  last_cost is the CURRENT
        # signature's entry — _InstrumentedJit reads it right after
        # check() to attribute the dispatch's FLOPs to the step.
        self._cost_by_sig: Dict[Tuple, Dict] = {}
        self.last_cost: Optional[Dict] = None
        reg = registry if registry is not None else get_registry()
        self._m_compiles = compile_counter(name, reg)
        self._m_recompiles = reg.counter(
            _RECOMPILES, "Signature changes after the first compile "
            "(shape/dtype/sharding churn)", labels=("fn",)
        ).labels(fn=name)

    def check(self, args: Any, kwargs: Dict, expected: bool = False,
              cost_fn: Optional[Callable[[], Dict]] = None) -> bool:
        """Record this call's signature (``args`` is any pytree — a tuple
        of positional args, or a position-keyed dict when the wrapper
        subsets by ``argnums``); returns True when it is new (i.e. this
        call compiles).  ``expected=True`` marks a PLANNED compile (e.g.
        serving AOT warmup sweeping its bucket shapes): it still counts
        in ``dl4j_compiles_total`` but does not warn or count as a
        recompile — those alert only on unplanned signature churn.

        ``cost_fn`` (profiler seam): called once per NEW signature to
        fetch its XLA cost analysis; the result is cached per signature,
        exposed as ``last_cost`` for every later call with that
        signature, and an UNEXPECTED recompile dumps the new abstract
        signature with its flops/bytes delta vs the evicted one into the
        flight recorder — not just a counter bump."""
        sig = fingerprint(args, kwargs)
        with self._lock:
            known = sig in self._seen
            if not known:
                self.compile_count += 1
                self._seen[sig] = self.compile_count
                self._m_compiles.inc()
            prev, self._last = self._last, sig
            if known:
                self.last_cost = self._cost_by_sig.get(sig)
                return False
        # cost analysis OUTSIDE the lock: it lowers + compiles
        cost: Optional[Dict] = None
        if cost_fn is not None:
            try:
                cost = cost_fn() or {}
            except Exception:
                cost = {}
            with self._lock:
                self._cost_by_sig[sig] = cost
        # dl4jlint: disable-next-line=lock-discipline -- GIL-atomic reference publish; readers are monitoring-grade and tolerate the brief pre-cost window
        self.last_cost = cost
        # compiles land in the flight record too: "what happened right
        # before the hang" is usually a compile or a shape change
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )

        get_flight_recorder().record(
            # dl4jlint: disable-next-line=lock-discipline -- reads back the ordinal this same call just assigned under the lock; a concurrent compile only skews the label
            "compile", fn=self.name, ordinal=self.compile_count,
            expected=bool(expected))
        if prev is not None and not expected:
            self.recompile_count += 1
            self._m_recompiles.inc()
            msg = self._delta_message(prev, sig, args, kwargs)
            self.warn(msg)
            self._record_recompile_event(prev, sig, cost)
        return True

    def _record_recompile_event(self, prev: Tuple, new: Tuple,
                                cost: Optional[Dict]) -> None:
        """The satellite-grade recompile record: new abstract signature +
        cost-analysis summary (flops/bytes delta vs the evicted
        signature) into the flight recorder.  Cost fields appear when a
        profiler had analysis enabled for both signatures."""
        from deeplearning4j_tpu.observability.flightrecorder import (
            get_flight_recorder,
        )

        ev: Dict[str, Any] = {
            # dl4jlint: disable-next-line=lock-discipline -- flight-record label read; exactness not load-bearing
            "fn": self.name, "ordinal": self.compile_count,
            "signature": _fmt_signature(new),
            "evicted_signature": _fmt_signature(prev),
        }
        with self._lock:
            prev_cost = self._cost_by_sig.get(prev)
        if cost:
            ev["flops"] = cost.get("flops")
            ev["bytes_accessed"] = cost.get("bytes_accessed")
        if prev_cost:
            ev["evicted_flops"] = prev_cost.get("flops")
            ev["evicted_bytes_accessed"] = prev_cost.get("bytes_accessed")
        if cost and prev_cost:
            ev["flops_delta"] = ((cost.get("flops") or 0.0)
                                 - (prev_cost.get("flops") or 0.0))
            ev["bytes_delta"] = ((cost.get("bytes_accessed") or 0.0)
                                 - (prev_cost.get("bytes_accessed") or 0.0))
        get_flight_recorder().record("recompile", **ev)

    def _delta_message(self, old: Tuple, new: Tuple, args, kwargs) -> str:
        old_def, old_leaves = old
        new_def, new_leaves = new
        parts: List[str] = []
        if old_def != new_def:
            parts.append("pytree structure changed")
        if len(old_leaves) == len(new_leaves):
            try:
                paths = _leaf_paths(args, kwargs)
            except Exception:
                paths = [f"leaf[{i}]" for i in range(len(new_leaves))]
            for i, (o, n) in enumerate(zip(old_leaves, new_leaves)):
                if o != n:
                    parts.append(f"{paths[i]}: {_fmt_leaf_sig(o)} -> "
                                 f"{_fmt_leaf_sig(n)}")
        else:  # e.g. a mask appearing mid-run (None -> array)
            parts.append(f"leaf count {len(old_leaves)} -> "
                         f"{len(new_leaves)}")
        delta = "; ".join(parts[:8]) or "signature changed"
        if len(parts) > 8:
            delta += f"; … {len(parts) - 8} more"
        # dl4jlint: disable-next-line=lock-discipline -- warning-text label read; exactness not load-bearing
        return (f"recompile #{self.compile_count} of {self.name}: {delta} "
                f"(each new signature costs an XLA compilation; pad/bucket "
                f"inputs to stable shapes to avoid this)")


class _InstrumentedJit:
    """Transparent wrapper: ``__call__`` runs the detector then the jitted
    fn; everything else (``lower``, ``trace``, ``clear_cache``…) delegates,
    so AOT-compile workflows keep working on the wrapped object.

    ``argnums`` restricts the fingerprint to those positional args — the
    fit loops pass only the DATA argument positions (batch, labels, masks,
    carries), because the params/optimizer-state pytrees cannot change
    abstract shape between steps (each step's inputs are the previous
    step's outputs) and fingerprinting hundreds of param leaves every
    iteration is measurable host overhead.

    Profiler seam: while a ``StepProfiler`` with cost analysis is
    installed, each NEW signature is cost-analyzed (abstract lowering of
    the FULL argument list — safe with donation, nothing executes) and
    every call reports its signature's cached flops/bytes to the profiler
    (``note_dispatch``), which rolls them into the step's MFU/roofline
    gauges at the ``step_guard`` boundary."""

    __slots__ = ("_fn", "detector", "_argnums")

    def __init__(self, fn: Callable, detector: RecompileDetector,
                 argnums: Optional[Tuple[int, ...]] = None):
        self._fn = fn
        self.detector = detector
        self._argnums = argnums

    def __call__(self, *args, **kwargs):
        prof = profiling.active_profiler()
        coll = shardstats.active_collector()
        cost_fn = None
        fn = self._fn
        if coll is not None:
            # superset analysis: memory_analysis + collective census +
            # the same flops/bytes fields jit_cost_analysis returns, from
            # ONE lower+compile — an installed profiler reads it as-is
            cost_fn = lambda: shardstats.program_analysis(fn, args, kwargs)
        elif prof is not None and prof.cost_analysis:
            cost_fn = lambda: profiling.jit_cost_analysis(fn, args, kwargs)
        if self._argnums is None:
            new = self.detector.check(args, kwargs, cost_fn=cost_fn)
        else:
            # dict keyed by the ORIGINAL position so delta paths stay
            # meaningful ("args[4]: f32[32,8] -> f32[20,8]")
            sel = {i: args[i] for i in self._argnums if i < len(args)}
            new = self.detector.check(sel, kwargs, cost_fn=cost_fn)
        if new:
            # a new signature compiles: the program as it now runs is what
            # program_scopes() describes (the FULL argument list, abstract)
            register_program(self.detector.name, self._fn, args, kwargs)
        if prof is not None:
            prof.note_dispatch(self.detector.name, self.detector.last_cost)
        if coll is not None:
            coll.note_dispatch(self.detector.name, self.detector.last_cost)
        return self._fn(*args, **kwargs)

    def __getattr__(self, item):
        return getattr(self._fn, item)

    def __repr__(self):
        return f"InstrumentedJit({self.detector.name})"


def instrument(fn: Callable, name: str, registry=None,
               warn: Optional[Callable[[str], None]] = None,
               argnums: Optional[Tuple[int, ...]] = None) -> _InstrumentedJit:
    """Wrap a jitted callable with a RecompileDetector (see module doc).
    ``argnums``: fingerprint only these positional args (hot-loop cost
    control; see ``_InstrumentedJit``)."""
    return _InstrumentedJit(fn, RecompileDetector(name, registry, warn),
                            None if argnums is None else tuple(argnums))


def compile_counter(fn_name: str, registry=None):
    """The shared ``dl4j_compiles_total{fn=}`` child for callers outside
    the detector (e.g. bench AOT compiles) — ONE owner for the family
    declaration, so label sets can never diverge."""
    from deeplearning4j_tpu.observability.metrics import get_registry

    reg = registry if registry is not None else get_registry()
    return reg.counter(
        _COMPILES, "Distinct abstract input signatures (≈ XLA "
        "compilations) per jitted function", labels=("fn",)).labels(
        fn=fn_name)


# ---------------------------------------------------------------------------
# what a compiled program says of itself: device operation -> scope path
# ---------------------------------------------------------------------------

class ScopeRow(NamedTuple):
    """One instruction of a compiled module."""

    name: str      # as XLA prints it, without the ``%``: ``fusion.12``
    shape: str     # its result's shape as XLA prints it, layout and all
    opcode: str    # ``fusion``, ``custom-call``, ``while``, ``all-reduce`` ...
    path: str      # scope path of its own ``op_name`` (``scope_path``)
    # scope path -> (instructions, of them matrix products and kernel calls)
    # of everything fused into it, or run by the computations it calls
    fused: Dict[str, Tuple[int, int]]
    # for an instruction without an ``op_name`` (XLA's own: the ``copy-done``
    # of a prefetched weight, a ``ConcatBitcast``), the nearest instruction
    # with one among those that consume its result; else ``""``
    consumer: str = ""


class ProgramScopes(NamedTuple):
    program: str               # the name it was registered under
    module: str                # the compiled module's own: ``jit_prefill_512``
    rows: Tuple[ScopeRow, ...]


# name -> the program traced at its abstract signature (``jax.stages.Traced``:
# the jaxpr, no Python callable and no argument).  One table a process; a name
# registered again (a new signature, a new model version) replaces its entry.
# Nothing here keeps a network, a parameter or a pool alive: an entry still
# answers after its owner has let everything go.
_PROGRAMS: Dict[str, Any] = {}
_PROGRAMS_LOCK = threading.Lock()


def _abstract(tree):
    """``tree`` with every array leaf as a ``jax.ShapeDtypeStruct`` of its
    shape, dtype, weak type and, where the array is committed to one, its
    sharding: what jit keys a program on.  (An uncommitted array lowers as
    an argument without a sharding does; given one here, the same program
    would lower to another module and miss the compile cache.)  A leaf that
    is no array (a static argument) stays as it is."""
    import jax

    def leaf(x):
        if isinstance(x, jax.ShapeDtypeStruct):
            return x
        try:
            aval = jax.typeof(x)
        except TypeError:
            return x
        sharding = x.sharding if getattr(x, "committed", False) else None
        return jax.ShapeDtypeStruct(aval.shape, aval.dtype, sharding=sharding,
                                    weak_type=aval.weak_type)

    return jax.tree_util.tree_map(leaf, tree)


def register_program(name: str, jitted: Callable, args: Tuple,
                     kwargs: Optional[Dict] = None) -> None:
    """Remember how to describe the program ``jitted(*args, **kwargs)``
    compiles to, under ``name``: ``jitted`` traced at the abstract signature
    of the full argument list.  Meant for a path that runs once a signature,
    just ahead of the call that compiles: ``instrument()``'s wrapper calls it
    on a detector miss, ``GenerationPrograms.warm()`` for ``decode`` and
    each ``prefill_<bucket>``.  The trace is the one the call itself needs
    (jit finds it in its cache and does not trace again), so nothing is
    added to the set-up; what is kept is the jaxpr alone."""
    traced = jitted.trace(*_abstract(tuple(args)),
                          **_abstract(dict(kwargs or {})))
    with _PROGRAMS_LOCK:
        _PROGRAMS[name] = traced


def registered_programs() -> List[str]:
    with _PROGRAMS_LOCK:
        return sorted(_PROGRAMS)


@contextlib.contextmanager
def _scopes_in_cache_key():
    """A persistent-cache hit returns the executable as the process that
    compiled it wrote it, ``op_name`` s included, and jax leaves them out of
    the key unless told otherwise: a program cached by older code would
    describe itself by that code's scopes.  ``backend.compile_cache.
    enable_compile_cache()`` tells it process-wide, so that this compile hits
    the entry the program itself was served from; here for a process that set
    its cache up another way (a miss then, never a stale table)."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def program_scopes(name: str) -> ProgramScopes:
    """The compiled module of the program registered as ``name``, by
    instruction: lowered and compiled from the kept trace (nothing executes,
    nothing is donated; with a persistent compile cache it is a hit), then
    read from the executable's text (jaxlib hands out the module's proto as
    bytes only; the text is what Python can read without a second package).
    Costs a compile-cache load and a parse of the text a call; nothing until
    called."""
    with _PROGRAMS_LOCK:
        traced = _PROGRAMS.get(name)
        known = sorted(_PROGRAMS)
    if traced is None:
        raise ValueError(f"no program registered as {name!r}; registered: "
                         f"{known}")
    with _scopes_in_cache_key():
        compiled = traced.lower().compile()
    module, rows = parse_module_text(compiled.as_text())
    return ProgramScopes(name, module, rows)


_WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
# scopes that name a traced FUNCTION, not a place in the model
_FUNCTION_WRAPPERS = ("jit", "pjit")


def scope_path(op_name: str) -> str:
    """The ``jax.named_scope`` path of an ``op_name``:
    ``jit(step)/transpose(jvp(layer_3))/ffn/dot_general`` -> ``layer_3/ffn``.
    jax wraps one component a transform (``jvp(...)``, ``transpose(...)``,
    ``vmap(...)``): the wrappers are peeled; a ``jit(...)`` component names a
    function and is dropped, as is the last component, the primitive.
    Where XLA merged instructions it joins their names with ``;``: the first
    is read."""
    parts = []
    for part in op_name.split(";", 1)[0].split("/")[:-1]:
        keep = True
        while (m := _WRAPPER.match(part)) is not None:
            keep = keep and m.group(1) not in _FUNCTION_WRAPPERS
            part = m.group(2)
        if keep and part:
            parts.append(part)
    return "/".join(parts)


_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_HEAD = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\{$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                     r"false_computation)=%?([\w.\-]+)")
_CALLED_LIST = re.compile(r"\b(?:branch_computations|called_computations)="
                          r"\{([^}]*)\}")
# not work of their own: they leave a fusion's tally alone
_PLUMBING = ("parameter", "constant", "tuple", "get-tuple-element", "bitcast")
_MATMULS = ("dot", "convolution", "custom-call", "ragged-dot")


def _balanced(text: str) -> int:
    """Index of the ``)`` that closes the ``(`` ``text`` starts with."""
    depth = 0
    for end, ch in enumerate(text):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            return end
    return len(text) - 1


class _Instruction(NamedTuple):
    """One line of HLO text, as ``parse_module_text`` first reads it."""

    name: str
    shape: str
    opcode: str
    path: Optional[str]        # None: no ``op_name`` of its own
    called: List[str]          # the computations it calls
    operands: List[str]


def _split_instruction(line: str):
    """``(name, shape, opcode, operand names)`` of one instruction line of
    HLO text."""
    name, rest = line.split(" = ", 1)
    if rest.startswith("("):                 # a tuple: balanced parentheses
        end = _balanced(rest)
        shape, rest = rest[:end + 1], rest[end + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
    opcode, _, args = rest.partition("(")
    operands = _OPERAND.findall(args[:_balanced("(" + args)])
    return name.lstrip("%"), shape, opcode, operands


def parse_module_text(text: str) -> Tuple[str, Tuple[ScopeRow, ...]]:
    """``(module name, rows)`` of a compiled module's ``as_text()``: a row
    for every instruction of every computation, in the text's order."""
    module, current = "", None
    bodies: Dict[str, List[_Instruction]] = {}    # by computation
    for raw in text.splitlines():
        line = raw.strip()
        if current is None:
            if not module and (m := _MODULE.match(line)):
                module = m.group(1)
            elif " = " not in line and (m := _HEAD.match(line)):
                current = bodies.setdefault(m.group(1), [])
        elif line == "}":
            current = None
        elif " = " in line:
            if line.startswith("ROOT "):
                line = line[5:]
            name, shape, opcode, operands = _split_instruction(line)
            m = _OP_NAME.search(line)
            called = _CALLED.findall(line)
            for group in _CALLED_LIST.findall(line):
                called += [c.strip().lstrip("%") for c in group.split(",")]
            # an op_name without a "/" is an argument's label (XLA's copy of
            # ``params['layer_1']['W']``), not a place in the program
            own = m is not None and "/" in m.group(1)
            current.append(_Instruction(
                name, shape, opcode, scope_path(m.group(1)) if own else None,
                called, operands))

    tallies: Dict[str, Dict[str, Tuple[int, int]]] = {}

    def add(out, called):
        for inner in called:
            for p, (n, k) in tally(inner).items():
                n0, k0 = out.get(p, (0, 0))
                out[p] = (n0 + n, k0 + k)
        return out

    def tally(comp: str) -> Dict[str, Tuple[int, int]]:
        """Scope path -> (instructions, matmuls) of a computation and of
        everything it calls."""
        if comp not in tallies:
            out = tallies[comp] = {}      # set first: a cycle ends here
            for ins in bodies.get(comp, ()):
                if ins.opcode not in _PLUMBING:
                    add(out, ins.called)
                    n, k = out.get(ins.path or "", (0, 0))
                    out[ins.path or ""] = (n + 1,
                                           k + (ins.opcode in _MATMULS))
        return tallies[comp]

    rows = []
    for lines in bodies.values():
        named = {ins.name for ins in lines if ins.path is not None}
        users: Dict[str, List[str]] = {}
        for ins in lines:
            for operand in ins.operands:
                users.setdefault(operand, []).append(ins.name)
        for ins in lines:
            consumer = ""
            if ins.path is None:
                # breadth first through what consumes it, plumbing included
                seen, queue = {ins.name}, list(users.get(ins.name, ()))
                while queue and not consumer:
                    user = queue.pop(0)
                    if user in named:
                        consumer = user
                    elif user not in seen:
                        seen.add(user)
                        queue += users.get(user, ())
            rows.append(ScopeRow(ins.name, ins.shape, ins.opcode,
                                 ins.path or "", add({}, ins.called),
                                 consumer))
    return module, tuple(rows)
