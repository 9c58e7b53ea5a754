"""The benchmark's one command.

    python3 -m benchmark.run --workload NAME --seed N --seconds T --trace 0|1

finds the cell ``NAME`` in ``BENCHMARK.json``, its configuration by the
file named there, its traffic mix in ``benchmark/traffic/<traffic>.json``,
the driver for that mix's ``job`` in ``benchmark/jobs/<job>.py`` and, for a
traced run, a reader for every per-layer metric that lists the cell in
``benchmark/metrics/<metric>.py``.  Adding a cell, a configuration, a kind
of job or a per-layer metric is a new file and a new manifest entry.

A run: set-up (build, weights from the seed, warm-up; timed from process
start to the opening of the window as ``setup_s``), the measured window of
``--seconds`` seconds, the device's peak memory, release of the program's
state, then the comparison with the plain reference that decides
``correct``.  The last line of standard output is the result; the numbers
compared are the last lines of standard error.

It fails, with no result line, on anything but a TPU with the chips the
cell asks for.  ``--rehearsal`` runs the same control flow at the toy sizes
of ``benchmark/rehearsal.json`` on any backend and prints a last line that
has no ``correct``, ``metrics`` or ``device`` key.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()          # set-up counts from here

import argparse                          # noqa: E402
import contextlib                        # noqa: E402
import importlib                         # noqa: E402
import json                              # noqa: E402
import os                                # noqa: E402
import shutil                            # noqa: E402
import sys                               # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

WINDOW_SPAN = "bench_window"


class CompileLog:
    """Counts real XLA compilations (persistent-cache hits included) from
    JAX's own monitoring events.  Copied from ``chip_smoke.py``."""

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, name, secs, **_):
        if name.startswith("/jax/core/compile/"):
            self.seconds += secs
            if name.endswith("backend_compile_duration"):
                self.compiles += 1

    def _event(self, name, **_):
        if name.endswith("/cache_hits"):
            self.cache_hits += 1
        elif name.endswith("/cache_misses"):
            self.cache_misses += 1


class Context:
    """What a job and the metric readers see of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)
        self.state = None          # the job's live objects, until release
        self.obs = {}              # the job's observations of the window
        self.trace = None          # trace_reduce.Reduced, traced runs only
        # the job wraps exactly its measured interval in this; a traced run
        # makes it the span the trace is clipped to
        self.window_span = contextlib.nullcontext


def load_manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json "
                     f"(has {[e['name'] for e in entries]})")


def metrics_of_cell(manifest: dict, kind: str, cell: str) -> list:
    return [m for m in manifest[kind]
            if cell in m.get("workloads", [cell])]


def load_reader(name: str):
    """A per-layer metric's reader, by the metric's name (which may hold
    dots): ``benchmark/metrics/<name>.py`` with ``read(ctx)``."""
    import importlib.util

    path = os.path.join(ROOT, "benchmark", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.metrics._reader_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def apply_rehearsal(config: dict, traffic: dict, limits: dict,
                    cell: str) -> None:
    with open(os.path.join(ROOT, "benchmark", "rehearsal.json")) as f:
        toy = json.load(f)
    limits.update(toy["limits"].get(cell, {}))
    config.update(toy["config"])
    for key, val in toy["traffic"].get(traffic["job"], {}).items():
        if isinstance(val, dict) and isinstance(traffic.get(key), dict):
            traffic[key].update(val)
        else:
            traffic[key] = val


def parse(argv):
    ap = argparse.ArgumentParser(prog="benchmark.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--describe", metavar="FILE", default=None,
                    help="with --trace 1: also write the trace's planes, "
                         "lines and heaviest names to FILE, to read by hand")
    return ap.parse_args(argv)


def make_context(args, fault=None):
    """(manifest, cell, job module, Context) of one run."""
    manifest = load_manifest()
    cell = find(manifest["workloads"], args.workload, "workload")
    cfg_entry = find(manifest["configs"], cell["config"], "configuration")
    from benchmark import flops, model, traffic_gen

    config = model.load_config(os.path.join(ROOT, cfg_entry["file"]))
    traffic = traffic_gen.load_traffic(cell["traffic"])
    limits_path = os.path.join(ROOT, "benchmark", "limits",
                               cell["name"] + ".json")
    with open(limits_path) as f:
        limits = json.load(f)["limits"]
    if args.rehearsal:
        apply_rehearsal(config, traffic, limits, cell["name"])

    import jax

    from deeplearning4j_tpu.backend.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    if not args.rehearsal:
        if platform != "tpu":
            raise SystemExit(f"benchmark: needs a TPU, JAX found {platform}")
        if len(devices) < cell["chips"]:
            raise SystemExit(f"benchmark: cell {cell['name']} needs "
                             f"{cell['chips']} chip(s), JAX found "
                             f"{len(devices)}")
    # a rehearsal only exercises the readers' code: its line is no result
    peaks = flops.peaks_for("TPU v5 lite" if args.rehearsal else kind)
    job = importlib.import_module("benchmark.jobs." + traffic["job"])
    ctx = Context(cell=cell, config=config, traffic=traffic, limits=limits,
                  seed=args.seed, seconds=args.seconds, traced=bool(args.trace),
                  rehearsal=args.rehearsal, fault=fault, peaks=peaks,
                  devices=devices[:cell["chips"]], compile_log=CompileLog(),
                  platform=platform, kind=kind,
                  cache_dir=cache_dir)
    return manifest, cell, job, ctx


def run(args, fault=None) -> dict:
    """One run; returns what is printed.  ``fault`` (tests only) names a
    fault for the job to plant under the timed path."""
    manifest, cell, job, ctx = make_context(args, fault)
    try:
        return _measure(args, manifest, cell, job, ctx)
    except BaseException:
        try:                      # leave no thread of the program behind
            job.release(ctx)
        except Exception:
            pass
        raise


def _measure(args, manifest, cell, job, ctx) -> dict:
    import jax

    traffic, platform, kind = ctx.traffic, ctx.platform, ctx.kind
    job.setup(ctx)
    setup_s = time.perf_counter() - T_PROCESS
    seconds = args.seconds
    trace_dir = None
    compiles_before = ctx.compile_log.compiles
    if ctx.traced:
        # a traced run's window is the traced interval, so the trace and
        # the job's own observations cover the same work
        seconds = min(seconds, float(traffic.get("trace_seconds", 3)))
        trace_dir = os.path.join(ROOT, ".bench_out",
                                 "trace-" + cell["name"])
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0       # named spans only; far less host cost
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ctx.window_span = lambda: jax.profiler.TraceAnnotation(WINDOW_SPAN)
        try:
            job.window(ctx, seconds)
        finally:
            jax.profiler.stop_trace()
    else:
        job.window(ctx, seconds)
    ctx.obs["window_compiles"] = ctx.compile_log.compiles - compiles_before
    stats = [d.memory_stats() or {} for d in ctx.devices]
    memory_peak = max((s.get("peak_bytes_in_use", 0) for s in stats),
                      default=0)
    job.release(ctx)
    checks = job.check(ctx)           # [(name, value, limit), ...]
    correct = all(v == v and v <= lim for _, v, lim in checks)

    device = {"platform": platform, "kind": kind, "count": len(ctx.devices),
              "memory_peak_bytes": int(memory_peak)}
    breakdown = None
    if ctx.traced:
        from benchmark import trace_reduce

        ctx.trace = trace_reduce.reduce_dir(trace_dir, WINDOW_SPAN)
        if args.describe:
            os.makedirs(os.path.dirname(os.path.abspath(args.describe)),
                        exist_ok=True)
            with open(args.describe, "w") as f:
                trace_reduce.describe(trace_dir, out=f)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        breakdown = ctx.trace.breakdown()
        values = {}
        for m in metrics_of_cell(manifest, "per_layer", cell["name"]):
            reader = load_reader(m["name"])
            val = reader.read(ctx)
            if val is not None:
                values[m["name"]] = {"value": float(val), "unit": m["unit"]}
    else:
        e2e = job.end_to_end(ctx)
        e2e["setup_s"] = setup_s
        values = {m["name"]: {"value": float(e2e[m["name"]]),
                              "unit": m["unit"]}
                  for m in metrics_of_cell(manifest, "end_to_end",
                                           cell["name"])}
    compared = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    line = {"correct": bool(correct), "attempted": ctx.obs["attempted"],
            "failed": ctx.obs["failed"], "metrics": values, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["notes"] = {"workload": cell["name"], "seed": args.seed,
                     "window_s": ctx.obs["window_s"], "setup_s": setup_s,
                     "setup_compiles": compiles_before,
                     "cache_hits": ctx.compile_log.cache_hits,
                     "cache_misses": ctx.compile_log.cache_misses,
                     "window_compiles": ctx.obs["window_compiles"],
                     **ctx.obs.get("notes", {})}
    line["compared"] = compared
    if args.rehearsal:
        # no "correct", "metrics" or "device": cannot be taken for a result
        line = {"rehearsal": True, "would_be_correct": bool(correct),
                "backend": platform, "attempted": line["attempted"],
                "failed": line["failed"], "values": values,
                "notes": line["notes"], "compared": compared}
    return line


def main(argv=None) -> int:
    args = parse(argv)
    line = run(args)
    for name, c in line["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
