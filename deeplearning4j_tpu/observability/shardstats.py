"""Memory & collective-communication observability: the sharding ledger.

PR 7 gave every compiled signature a FLOPs number (``jit_cost_analysis``)
and PR 1 gave the process PJRT device-memory gauges — but nothing reports
the third axis: WHERE the bytes live and WHAT the collectives move.
ROADMAP item 2 (ZeRO-style sharding of the weight update, arXiv
2004.13336) cannot land against guesses; this module provides the
measured baselines it will regress against, in the memory-accounting
spirit of "Memory-efficient array redistribution" (arXiv 2112.01075):

- **Per-program HLO accounting** (``program_analysis``): the compiled
  step's ``memory_analysis()`` (argument/output/temp/alias bytes →
  ``dl4j_program_memory_bytes{fn,kind}``) plus a **collective census**
  of the compiled HLO text — count and payload bytes of every
  ``all-reduce`` / ``all-gather`` / ``reduce-scatter`` /
  ``collective-permute`` / ``all-to-all`` instruction, with the replica
  group size recovered where the HLO records it
  (``dl4j_step_collective_bytes{fn,op}`` /
  ``dl4j_step_collectives_total{fn,op}``).  Harvested once per abstract
  signature through the ``RecompileDetector.check(cost_fn=)`` seam —
  exactly like ``jit_cost_analysis``, on ``ShapeDtypeStruct``s, so
  donated buffers are never touched and nothing executes.
- **The sharding ledger** (``sharding_ledger`` / ``record_ledger``):
  walk params/updater/net-state pytrees with their ACTUAL shardings and
  report per-device bytes, replication factor per tree and subtree, and
  a projected-ZeRO column (bytes per device if the tree were
  reduce-scattered over the data axis) →
  ``dl4j_sharded_bytes{component,tree}`` /
  ``dl4j_replication_factor{component,tree}`` plus the human-readable
  ``format_ledger`` report.  The walk reads only shape/dtype/sharding
  metadata — never a buffer, never a device sync.
- **A comm roofline**: a per-backend link-bandwidth table
  (``LINK_BANDWIDTH`` — single owner, like ``profiling.PEAK_FLOPS``)
  turns censused collective bytes into estimated comm seconds per step
  and a comm/compute ratio
  (``dl4j_step_comm_seconds{fn}`` /
  ``dl4j_step_comm_compute_ratio{fn}``).

Census caveats (docs/observability.md "Memory & communication"): the
census counts instructions in the compiled module ONCE — a collective
inside a ``while``/``scan`` body executes once per trip but is counted
once; XLA may fuse several logical all-reduces into one variadic
instruction (the BYTES stay right, the COUNT drops); and bytes are
payload bytes (max of operand/result size), not wire bytes — the
roofline applies the ring factor, the census does not.

Hot-loop cost while a collector is installed: one dict-identity check
plus a few cached counter increments per dispatch; the lower+compile
for the census happens once per NEW signature (steady state: never).
"""

from __future__ import annotations

import logging
import math
import re
import threading
from typing import Any, Dict, List, Optional, Tuple

_PROGRAM_MEMORY = "dl4j_program_memory_bytes"
_COLL_BYTES = "dl4j_step_collective_bytes"
_COLL_TOTAL = "dl4j_step_collectives_total"
_COMM_SECONDS = "dl4j_step_comm_seconds"
_COMM_RATIO = "dl4j_step_comm_compute_ratio"
_LINK_BW = "dl4j_link_bandwidth_bytes_per_s"
_SHARDED_BYTES = "dl4j_sharded_bytes"
_REPLICATION = "dl4j_replication_factor"

# ---------------------------------------------------------------- bandwidth
# Per-chip interconnect (ICI) bandwidth, bytes/s, all links combined.
# The ONE owner of the table: the comm roofline reads it from here (same
# single-owner discipline as ``profiling.PEAK_FLOPS``, and like it keyed
# by the EXACT ``device_kind`` string the runtime reports).  Every consumer labels the derived seconds
# as estimates.
LINK_BANDWIDTH = {
    # TPU v5e: 1,600 Gbit/s per chip (Google Cloud documentation, "TPU v5e")
    "TPU v5 lite": 200e9,
}

# ESTIMATE: on the virtual host-platform mesh a "collective" is a memcpy
# through shared DRAM; one socket sustains O(10) GB/s effective through
# an XLA:CPU all-reduce.  Order-of-magnitude only — every consumer
# labels CPU-derived comm seconds as an estimate (the honest-labeling
# discipline of ``profiling.CPU_PEAK_FLOPS_ESTIMATE``).
CPU_LINK_BANDWIDTH_ESTIMATE = 10e9


def link_bandwidth_for(device=None) -> Tuple[float, str]:
    """(link bandwidth bytes/s, source) for a jax device (default:
    ``devices()[0]``).  source: ``"table"`` (spec sheet, exact
    ``device_kind``) or ``"cpu-estimate"`` (documented estimate).  An
    accelerator that is not in the table raises."""
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = device.device_kind
    if kind in LINK_BANDWIDTH:
        return LINK_BANDWIDTH[kind], "table"
    if device.platform == "cpu":
        return CPU_LINK_BANDWIDTH_ESTIMATE, "cpu-estimate"
    raise ValueError(
        f"no link bandwidth on record for device_kind {kind!r} (platform "
        f"{device.platform!r}); known: {sorted(LINK_BANDWIDTH)}")


def ring_wire_bytes(op: str, payload_bytes: float,
                    group_size: Optional[int]) -> float:
    """Bytes through each device's link for one collective, ring
    algorithm (the scaling-book recipe):
    all-reduce moves ``2(g-1)/g * payload``; all-gather/reduce-scatter
    half that; a permute moves the payload once.  Unknown group size
    falls back to the payload (a lower bound, labeled as such)."""
    g = group_size or 0
    if g < 2:
        return float(payload_bytes)
    if op == "all-reduce":
        return 2.0 * (g - 1) / g * payload_bytes
    if op in ("all-gather", "reduce-scatter"):
        return (g - 1) / g * payload_bytes
    return float(payload_bytes)


# ------------------------------------------------------------------- census
_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")

# one HLO shape token: dtype[dims]{layout?} — the layout braces may hold
# TPU tile annotations with parens ({0:T(8,128)}), but never nested braces
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\](?:\{[^}]*\})?")
# an instruction line: "%name = <shape or (tuple)> <op>(" — the op name
# token directly before the open paren is what defines the instruction
# (operand shapes inside the parens must not match).  The tuple
# alternative must tolerate one level of nested parens: post-layout TPU
# HLO writes tuple results like "(f32[1024]{0:T(1024)}, ...)", and a
# first-)-stops scan would drop exactly the variadic/async collectives
# the census exists to count.
_INSTR_RE = re.compile(
    # single-char inner alternation, NOT "[^()]+": a nested + inside *
    # backtracks exponentially on long non-matching paren runs
    r"=\s*(\((?:[^()]|\([^()]*\))*\)"
    r"|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(" + "|".join(COLLECTIVE_OPS) + r")(-start)?\(")
# iota form: replica_groups=[groups,size]<=[n...] ; explicit form:
# replica_groups={{0,1},{2,3}}
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")


def _shape_bytes(token: str) -> int:
    """Bytes of one HLO shape token (or a tuple of them)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(token):
        size = _DTYPE_BYTES.get(dtype)
        if size is None:
            continue  # token/opaque types carry no accountable payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * size
    return total


def _group_size(line: str) -> Optional[int]:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2)) or None
    m = _GROUPS_LIST_RE.search(line)
    if m:
        return len([t for t in m.group(1).split(",") if t.strip()])
    return None


def collective_census(hlo_text: str) -> Dict[str, Dict[str, Any]]:
    """Count and size every collective instruction in compiled HLO text.

    Returns ``{op: {"count": n, "bytes": payload_bytes,
    "group_sizes": [...]}}`` — ``bytes`` is the payload (max of result
    and operand bytes, so all-gather counts the gathered tensor and
    reduce-scatter the pre-scatter one), NOT wire bytes (see
    ``ring_wire_bytes``).  Async ``-start`` instructions count once;
    their ``-done`` halves carry no shape work and never match."""
    out: Dict[str, Dict[str, Any]] = {}
    for line in hlo_text.splitlines():
        m = _INSTR_RE.search(line)
        if m is None:
            continue
        result_tok, op = m.group(1), m.group(2)
        # operand list: everything inside the instruction's parens —
        # balanced-paren scan not needed, shapes never nest parens
        operands = line[m.end():line.rfind(")")]
        res_b, opnd_b = _shape_bytes(result_tok), _shape_bytes(operands)
        if m.group(3):
            # async -start: the result is an (operand, result) tuple, so
            # res_b double-counts — the payload is the larger half
            payload = max(res_b - opnd_b, opnd_b)
        else:
            payload = max(res_b, opnd_b)
        entry = out.setdefault(op, {"count": 0, "bytes": 0,
                                    "group_sizes": []})
        entry["count"] += 1
        entry["bytes"] += payload
        g = _group_size(line)
        if g is not None and g not in entry["group_sizes"]:
            entry["group_sizes"].append(g)
    return out


def attribute_mesh_axes(census: Dict[str, Dict[str, Any]],
                        axis_sizes: Dict[str, int]) -> Dict[str, List[str]]:
    """Best-effort mesh-axis attribution: an op whose replica group size
    equals the size of exactly ONE mesh axis is attributed to that axis
    (a 2-D mesh with equal axis sizes stays honest and unattributed)."""
    out: Dict[str, List[str]] = {}
    for op, entry in census.items():
        axes: List[str] = []
        for g in entry.get("group_sizes", ()):
            named = [a for a, s in axis_sizes.items() if s == g]
            if len(named) == 1 and named[0] not in axes:
                axes.append(named[0])
        out[op] = axes
    return out


def program_analysis(fn, args: Tuple, kwargs: Dict, *,
                     cost: bool = True, memory: bool = True,
                     collectives: bool = True) -> Dict[str, Any]:
    """The full per-program accounting at the ABSTRACT signature of
    ``args``/``kwargs`` (every array leaf replaced by a
    ``ShapeDtypeStruct`` — donated buffers never touched, nothing
    executes): XLA cost analysis (flops/bytes — the ONE owner of that
    recipe; ``profiling.jit_cost_analysis`` delegates here, and an
    installed ``StepProfiler`` reads this dict unchanged),
    ``memory_analysis()`` byte kinds, and the collective census of the
    compiled HLO.  The section flags skip work callers don't need
    (``as_text`` on a big program is not free).  ``{}`` when the
    backend supports none of it."""
    import jax
    from jax.sharding import NamedSharding

    def absify(leaf):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None or dtype is None:
            return leaf
        # the sharding must ride into the abstract signature: a jit
        # without explicit in_shardings (ParallelWrapper's fit_window)
        # gets its layout from the ARGUMENTS, and lowering without it
        # would compile a collective-free single-device program —
        # exactly the bytes this census exists to count
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding):
            return jax.ShapeDtypeStruct(tuple(shape), dtype,
                                        sharding=sharding)
        return jax.ShapeDtypeStruct(tuple(shape), dtype)

    try:
        abs_args, abs_kwargs = jax.tree_util.tree_map(absify, (args, kwargs))
        compiled = fn.lower(*abs_args, **abs_kwargs).compile()
    except Exception:
        return {}
    out: Dict[str, Any] = {}
    if cost:
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            out["flops"] = float(ca.get("flops", 0.0) or 0.0)
            out["bytes_accessed"] = float(
                ca.get("bytes accessed", 0.0) or 0.0)
        except Exception:
            pass
    if memory:
        try:
            ma = compiled.memory_analysis()
            out["memory"] = {
                "argument": int(ma.argument_size_in_bytes),
                "output": int(ma.output_size_in_bytes),
                "temp": int(ma.temp_size_in_bytes),
                "alias": int(ma.alias_size_in_bytes),
                "generated_code": int(ma.generated_code_size_in_bytes),
            }
        except Exception:
            pass
    if collectives:
        try:
            census = collective_census(compiled.as_text())
            out["collectives"] = census
            out["collective_bytes"] = float(
                sum(e["bytes"] for e in census.values()))
            out["collective_count"] = int(
                sum(e["count"] for e in census.values()))
        except Exception:
            pass
    return out


# ------------------------------------------------------------------ ledger
# Reserved updater-state subtrees the ZeRO update sharding keeps
# REPLICATED (stacked per replica in the wrapper): the stability engine's
# guard/scale scalars, the introspection stat vectors, and the numerics
# precision-ledger vector.  Mirrors ``resilience.stability.STATE_KEY`` /
# ``observability.introspection.STATE_KEY`` / ``observability.numerics
# .STATE_KEY`` — literals here so the ledger stays importable without
# jax; ``tests/test_zero.py`` pins the mirror.
RESERVED_REPLICATED_SUBTREES = ("__stability__", "__introspect__",
                                "__numerics__")


def zero_shardable(shape, k: int) -> bool:
    """Whether a leaf of ``shape`` participates in ZeRO update sharding
    over a ``k``-way data axis: its leading dimension must exist and
    divide evenly (a non-dividing leaf stays replicated — padding a
    shard would change the updater's elementwise math for schedules
    that read positions).  The ONE owner of the predicate: the
    projected-ZeRO ledger column and ``parallel.zero``'s actual layout
    both call this, which is what makes the projection testable against
    the real thing."""
    shape = tuple(shape)
    return (k > 1 and len(shape) >= 1 and shape[0] > 0
            and shape[0] % k == 0)


def _projected_zero_bytes(tree, k: int, reserved: bool = False) -> int:
    """Per-device bytes of ONE logical copy of ``tree`` under ZeRO
    update sharding: shardable leaves contribute 1/k of their bytes,
    non-dividing leaves and reserved subtrees (``__stability__`` /
    ``__introspect__``) stay replicated and contribute in full.  Walks
    shape/dtype metadata only."""
    import jax

    total = 0
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        acc = _leaf_accounting(leaf)
        if acc is None:
            continue
        top = getattr(path[0], "key", None) if path else None
        if (reserved or top in RESERVED_REPLICATED_SUBTREES
                or not zero_shardable(getattr(leaf, "shape", ()), k)):
            total += acc["global"]
        else:
            total += -(-acc["global"] // k)          # ceil
    return total


def _leaf_accounting(leaf) -> Optional[Dict[str, Any]]:
    """Shape/dtype/sharding metadata of one leaf — NEVER reads a buffer.
    None for non-array leaves (python scalars ride replicated for free)."""
    shape = getattr(leaf, "shape", None)
    dtype = getattr(leaf, "dtype", None)
    if shape is None or dtype is None:
        return None
    import numpy as np

    try:
        itemsize = np.dtype(dtype).itemsize
    except Exception:
        return None
    global_bytes = int(math.prod(tuple(shape)) * itemsize)
    per_device = global_bytes
    ndev = 1
    sharding = getattr(leaf, "sharding", None)
    if sharding is not None:
        try:
            shard_shape = sharding.shard_shape(tuple(shape))
            per_device = int(math.prod(shard_shape) * itemsize)
            ndev = int(getattr(sharding, "num_devices", None)
                       or len(sharding.device_set))
        except Exception:
            pass
    return {"global": global_bytes, "per_device": per_device,
            "devices": ndev, "stored": per_device * ndev}


def _tree_row(tree, logical_tree=None,
              data_axis_size: Optional[int] = None,
              reserved: bool = False) -> Dict[str, Any]:
    """One ledger row: aggregate byte accounting of a pytree under its
    actual shardings.  ``logical_tree`` is the SINGLE-MODEL tree when
    ``tree`` is a stacked replica view (ParallelWrapper's [K, ...]
    leaves) — its bytes define the replication denominator; default:
    the tree's own global bytes (right for replicated-sharding layouts,
    where the global array IS one logical copy)."""
    import jax

    glob = per_dev = stored = 0
    ndev = 1
    for leaf in jax.tree_util.tree_leaves(tree):
        acc = _leaf_accounting(leaf)
        if acc is None:
            continue
        glob += acc["global"]
        per_dev += acc["per_device"]
        stored += acc["stored"]
        ndev = max(ndev, acc["devices"])
    logical = glob
    if logical_tree is not None:
        logical = 0
        for leaf in jax.tree_util.tree_leaves(logical_tree):
            acc = _leaf_accounting(leaf)
            if acc is not None:
                logical += acc["global"]
    row: Dict[str, Any] = {
        "logical_bytes": logical,
        "global_bytes": glob,
        "per_device_bytes": per_dev,
        "stored_bytes": stored,
        "devices": ndev,
        "replication_factor": (round(stored / logical, 4) if logical
                               else 1.0),
    }
    k = data_axis_size or ndev
    if logical and k > 1:
        # projected-ZeRO column (arXiv 2004.13336): one logical copy
        # under ZeRO update sharding over the data axis — per LEAF, so
        # non-dividing leaves and the reserved replicated subtrees
        # project at full size exactly as ``parallel.zero`` lays them
        # out (the projection-vs-actual test in tests/test_zero.py
        # holds the two to each other).  Walked over the LOGICAL tree
        # when one is given (the stacked wrapper view's leaves carry a
        # leading replica axis that must not drive the predicate).
        projected = _projected_zero_bytes(
            logical_tree if logical_tree is not None else tree, k,
            reserved=reserved)
        row["zero_projected_per_device_bytes"] = projected
        row["zero_savings_per_device_bytes"] = per_dev - projected
    return row


def sharding_ledger(trees: Dict[str, Any],
                    logical_trees: Optional[Dict[str, Any]] = None,
                    data_axis_size: Optional[int] = None,
                    subtree_depth: int = 1) -> Dict[str, Any]:
    """The ledger over named trees (``{"params": ..., "updater_state":
    ..., "net_state": ...}``): one aggregate row per tree plus rows for
    each top-level subtree (layer / updater slot) so the report answers
    "which subtree is replicated how much" — the per-subtree factor is
    what the ZeRO PR flips for the optimizer moments."""
    logical_trees = logical_trees or {}
    out: Dict[str, Any] = {"trees": {}, "data_axis_size": data_axis_size}
    total = {"logical_bytes": 0, "per_device_bytes": 0, "stored_bytes": 0}
    for name, tree in trees.items():
        if tree is None:
            continue
        logical = logical_trees.get(name)
        row = _tree_row(tree, logical, data_axis_size)
        if subtree_depth > 0 and isinstance(tree, dict):
            subs = {}
            for key, sub in tree.items():
                sub_logical = (logical.get(key)
                               if isinstance(logical, dict) else None)
                subs[str(key)] = _tree_row(
                    sub, sub_logical, data_axis_size,
                    reserved=key in RESERVED_REPLICATED_SUBTREES)
            if subs:
                row["subtrees"] = subs
        out["trees"][name] = row
        for f in total:
            total[f] += row[f]
    total["replication_factor"] = (
        round(total["stored_bytes"] / total["logical_bytes"], 4)
        if total["logical_bytes"] else 1.0)
    out["total"] = total
    return out


def format_ledger(ledger: Dict[str, Any], component: str = "") -> str:
    """Human-readable ledger report (the operator view; JSON stays the
    machine form)."""
    def mb(b):
        return f"{b / 1e6:10.3f}"

    lines = [f"sharding ledger{' — ' + component if component else ''}"
             + (f" (data axis: {ledger.get('data_axis_size')})"
                if ledger.get("data_axis_size") else ""),
             f"{'tree':<28} {'logical MB':>10} {'per-dev MB':>10} "
             f"{'repl':>6} {'ZeRO MB':>10}"]
    for name, row in ledger.get("trees", {}).items():
        zero = row.get("zero_projected_per_device_bytes")
        lines.append(
            f"{name:<28} {mb(row['logical_bytes'])} "
            f"{mb(row['per_device_bytes'])} "
            f"{row['replication_factor']:>6.2f} "
            f"{mb(zero) if zero is not None else '        —'}")
        for sub, srow in (row.get("subtrees") or {}).items():
            szero = srow.get("zero_projected_per_device_bytes")
            lines.append(
                f"  {sub:<26} {mb(srow['logical_bytes'])} "
                f"{mb(srow['per_device_bytes'])} "
                f"{srow['replication_factor']:>6.2f} "
                f"{mb(szero) if szero is not None else '        —'}")
    t = ledger.get("total")
    if t:
        lines.append(
            f"{'TOTAL':<28} {mb(t['logical_bytes'])} "
            f"{mb(t['per_device_bytes'])} {t['replication_factor']:>6.2f}")
    return "\n".join(lines)


# ---------------------------------------------------- ledger store + gauges
_ledger_lock = threading.Lock()
_ledgers: Dict[str, Dict[str, Any]] = {}


def record_ledger(component: str, trees: Dict[str, Any],
                  logical_trees: Optional[Dict[str, Any]] = None,
                  data_axis_size: Optional[int] = None,
                  registry=None,
                  notes: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Compute the ledger, mirror the per-tree rows into
    ``dl4j_sharded_bytes`` / ``dl4j_replication_factor`` gauges, stash
    it for ``latest_ledgers()`` (flight dumps, ``GET /memory``, bench),
    and drop a ``sharding_ledger`` flight event.  O(tree leaves) of
    host metadata work; called at fit entry / device placement — never
    per step.  Best-effort: the fit loops and masters call this
    unguarded on their critical path, so a failure here logs and
    returns ``{}`` instead of aborting training (same contract as the
    flight-dump sections)."""
    try:
        return _record_ledger(component, trees, logical_trees,
                              data_axis_size, registry, notes)
    except Exception:
        logging.getLogger("deeplearning4j_tpu.observability").debug(
            "sharding ledger for %s failed", component, exc_info=True)
        return {}


def _record_ledger(component, trees, logical_trees, data_axis_size,
                   registry, notes=None) -> Dict[str, Any]:
    from deeplearning4j_tpu.observability.metrics import get_registry

    ledger = sharding_ledger(trees, logical_trees, data_axis_size)
    ledger["component"] = str(component)
    if notes:
        # layout provenance (e.g. update_sharding="zero" and which
        # reserved subtrees stayed replicated) — the operator-facing
        # record the ZeRO docs promise
        ledger["notes"] = dict(notes)
    reg = registry if registry is not None else get_registry()
    g_bytes = reg.gauge(
        _SHARDED_BYTES, "Per-device bytes of a tracked pytree under its "
        "actual shardings (ledger row; see docs/observability.md "
        "\"Memory & communication\")", labels=("component", "tree"))
    g_repl = reg.gauge(
        _REPLICATION, "Replication factor of a tracked pytree: bytes "
        "stored across all devices / bytes of one logical copy (K for "
        "K-replica replicated data parallel, ~1 under "
        "update_sharding='zero')", labels=("component", "tree"))
    for name, row in ledger["trees"].items():
        g_bytes.set(row["per_device_bytes"], component=component, tree=name)
        g_repl.set(row["replication_factor"], component=component, tree=name)
    with _ledger_lock:
        _ledgers[str(component)] = ledger
    from deeplearning4j_tpu.observability.flightrecorder import (
        get_flight_recorder,
    )

    get_flight_recorder().record(
        "sharding_ledger", component=component,
        data_axis_size=data_axis_size,
        total_per_device_bytes=ledger["total"]["per_device_bytes"],
        replication_factor=ledger["total"]["replication_factor"])
    return ledger


def record_model_ledger(net, component: str,
                        data_axis_size: Optional[int] = None,
                        registry=None) -> Dict[str, Any]:
    """Ledger of a model facade's params / updater state / net state —
    the one-call form the fit loops use."""
    return record_ledger(
        component,
        {"params": getattr(net, "params", None),
         "updater_state": getattr(net, "updater_state", None),
         "net_state": getattr(net, "net_state", None)},
        data_axis_size=data_axis_size, registry=registry)


def latest_ledgers() -> Dict[str, Dict[str, Any]]:
    """Most recent ledger per component (for flight dumps, the UI
    ``GET /memory`` endpoint, and the bench memory section)."""
    with _ledger_lock:
        return dict(_ledgers)


def clear_ledgers() -> None:
    """Test isolation."""
    with _ledger_lock:
        _ledgers.clear()


# --------------------------------------------------------------- collector
class ShardStatsCollector:
    """Per-program memory + collective accounting, harvested through the
    ``RecompileDetector.check(cost_fn=)`` seam.

    Usage::

        coll = ShardStatsCollector().install()
        net.fit(batches)        # census + memory gauges fill per program
        print(coll.programs())  # {fn: {memory, collectives, comm_*}}
        coll.uninstall()

    or as a context manager.  While installed, every ``instrument``-
    wrapped jitted function is analyzed ONCE per new abstract signature
    (``program_analysis`` — abstract lowering, donation-safe) and every
    dispatch bumps the collective counters from the cached census.  The
    analysis dict includes the ``jit_cost_analysis`` fields, so a
    concurrently installed ``StepProfiler`` keeps its MFU attribution
    from the same single lower+compile.
    """

    def __init__(self, registry=None, link_bandwidth: Optional[float] = None,
                 peak_flops: Optional[float] = None):
        from deeplearning4j_tpu.observability.metrics import get_registry
        from deeplearning4j_tpu.observability.profiling import peak_flops_for

        reg = registry if registry is not None else get_registry()
        self._registry = reg
        if link_bandwidth is not None:
            self.link_bandwidth, self.link_source = (float(link_bandwidth),
                                                     "override")
        else:
            self.link_bandwidth, self.link_source = link_bandwidth_for()
        if peak_flops is not None:
            self.peak_flops = float(peak_flops)
        else:
            self.peak_flops, _src = peak_flops_for()
        self._m_mem = reg.gauge(
            _PROGRAM_MEMORY, "Compiled-program memory_analysis() bytes per "
            "jitted function (kind: argument / output / temp / alias / "
            "generated_code), refreshed once per abstract signature",
            labels=("fn", "kind"))
        self._m_coll_bytes = reg.counter(
            _COLL_BYTES, "Collective payload bytes dispatched per jitted "
            "function and HLO op (census of the compiled program, counted "
            "once per call; collectives inside scan/while bodies are "
            "counted once per dispatch, not per trip)",
            labels=("fn", "op"))
        self._m_coll_total = reg.counter(
            _COLL_TOTAL, "Collective instructions dispatched per jitted "
            "function and HLO op (same census/caveats as "
            "dl4j_step_collective_bytes)", labels=("fn", "op"))
        self._m_comm_s = reg.gauge(
            _COMM_SECONDS, "Estimated communication seconds per step of "
            "the current compiled program: ring wire bytes over the "
            "backend link bandwidth (spec table on TPU, documented "
            "estimate on CPU)", labels=("fn",))
        self._m_ratio = reg.gauge(
            _COMM_RATIO, "Estimated comm/compute ratio of the current "
            "compiled program: comm seconds (link-bandwidth roofline) / "
            "compute seconds (flops over peak); > 1 means the step is "
            "communication-bound", labels=("fn",))
        self._m_bw = reg.gauge(
            _LINK_BW, "Link bandwidth assumed by the comm roofline "
            "(spec-sheet table for TPUs; on CPU a documented "
            "order-of-magnitude estimate)", labels=("source",))
        self._lock = threading.Lock()
        # fn -> {id(analysis dict): [(counter child, amount), ...]} —
        # the per-dispatch fast path is a dict-identity lookup + cached
        # incs.  Keyed per analysis id, not one slot per fn: a function
        # alternating between two live signatures (full batch /
        # remainder batch) must not re-absorb on every flip.  Bounded by
        # the detector's per-signature cost cache, which keeps the
        # analysis dicts (and so their ids) alive.
        self._dispatch_cache: Dict[str, Dict[int, List]] = {}
        self._programs: Dict[str, Dict[str, Any]] = {}

    # ------------------------------------------------------------ lifecycle
    def install(self) -> "ShardStatsCollector":
        global _active
        self._m_bw.set(self.link_bandwidth, source=self.link_source)
        _active = self
        return self

    def uninstall(self) -> None:
        global _active
        if _active is self:
            _active = None

    def __enter__(self) -> "ShardStatsCollector":
        return self.install()

    def __exit__(self, *exc) -> None:
        self.uninstall()

    # -------------------------------------------------------------- harvest
    def note_dispatch(self, fn_name: str, analysis: Optional[Dict]) -> None:
        """Called by ``recompile._InstrumentedJit`` per call with the
        dispatched signature's cached ``program_analysis`` dict.  First
        sight of a dict refreshes the program gauges; every call bumps
        the collective counters from the cached census."""
        if not analysis or ("collectives" not in analysis
                            and "memory" not in analysis):
            # a flops-only dict (profiler-era signature analyzed before
            # this collector was installed) carries NO census: absorbing
            # it would report a confidently wrong zero for a program
            # that may all-reduce megabytes — absent beats wrong
            return
        key = id(analysis)
        # dl4jlint: disable-next-line=lock-discipline -- GIL-atomic dict read on the dispatch fast path; a racing writer at worst causes one redundant _absorb of the same analysis (gauge re-set, idempotent)
        cached = self._dispatch_cache.get(fn_name)
        if cached is None or key not in cached:
            incs = self._absorb(fn_name, analysis)
            with self._lock:
                cached = dict(self._dispatch_cache.get(fn_name) or {})
                cached[key] = incs
                self._dispatch_cache[fn_name] = cached
        for child, amount in cached[key]:
            child.inc(amount)

    def _absorb(self, fn_name: str, analysis: Dict) -> List:
        """Signature-change slow path: set the program gauges, compute
        the roofline, and build the per-dispatch increment list."""
        incs: List = []
        for kind, b in (analysis.get("memory") or {}).items():
            self._m_mem.set(b, fn=fn_name, kind=kind)
        census = analysis.get("collectives") or {}
        wire = 0.0
        for op, entry in census.items():
            incs.append((self._m_coll_bytes.labels(fn=fn_name, op=op),
                         float(entry["bytes"])))
            incs.append((self._m_coll_total.labels(fn=fn_name, op=op),
                         float(entry["count"])))
            gs = entry.get("group_sizes") or [None]
            # one group size per op in practice; a mixed-size variadic
            # op uses the first recovered size for the ring factor
            wire += ring_wire_bytes(op, entry["bytes"], gs[0])
        comm_s = (wire / self.link_bandwidth if self.link_bandwidth > 0
                  else None)
        flops = analysis.get("flops") or 0.0
        compute_s = (flops / self.peak_flops
                     if flops > 0 and self.peak_flops > 0 else None)
        if comm_s is not None:
            self._m_comm_s.set(comm_s, fn=fn_name)
        ratio = None
        if comm_s is not None and compute_s:
            ratio = comm_s / compute_s
            self._m_ratio.set(ratio, fn=fn_name)
        with self._lock:
            self._programs[fn_name] = {
                "memory": analysis.get("memory"),
                "collectives": census,
                "collective_bytes": analysis.get("collective_bytes", 0.0),
                "collective_count": analysis.get("collective_count", 0),
                "wire_bytes_per_device": wire,
                "comm_seconds_estimate": comm_s,
                "compute_seconds_estimate": compute_s,
                "comm_compute_ratio": ratio,
                "flops": analysis.get("flops"),
            }
        return incs

    def analyze_program(self, fn, name: str, args: Tuple,
                        kwargs: Optional[Dict] = None) -> Dict[str, Any]:
        """Analyze a jitted callable OUTSIDE the instrument seam (the
        generation warmup and the grad-sync CLI own raw ``jax.jit``
        objects): runs ``program_analysis`` at the abstract signature
        and absorbs the result under ``name`` (gauges set, census
        cached; per-dispatch counters are the caller's to bump via
        ``note_dispatch`` if it dispatches repeatedly)."""
        analysis = program_analysis(fn, tuple(args), dict(kwargs or {}))
        if analysis:
            incs = self._absorb(name, analysis)   # takes the lock itself
            with self._lock:
                self._dispatch_cache[name] = {id(analysis): incs}
        return analysis

    def programs(self) -> Dict[str, Dict[str, Any]]:
        """Per-function accounting snapshot (bench memory section and
        ``GET /memory``)."""
        with self._lock:
            return {k: dict(v) for k, v in self._programs.items()}


_active: Optional[ShardStatsCollector] = None


def active_collector() -> Optional[ShardStatsCollector]:
    """The installed collector, or None (lock-free read: module-global
    assignment is atomic)."""
    return _active
