#!/usr/bin/env python3
"""The benchmark: one run of one cell on the chips of this machine.

    python3 bench/run.py --workload nytimes.train --seed 7 --seconds 30 \\
        --trace 0

A cell is ``bench/workloads/<name>.json``: its configuration
(``bench/configs/<config>.json``), its traffic kind, run by
``bench/traffic/<traffic>.py``, the chips it needs and the limits of the
numbers its correctness check compares.  Per-layer metrics are the
readers ``bench/metrics/<metric>.py``; each returns its number, or
``None`` where the run has nothing for it to read.  The harness finds all
of these by name and lists none of them.

Set-up — process start to the first timed sweep, compiles or loads from
JAX's persistent cache in ``<checkout>/.jax_cache`` included — is
``setup_s``.  The window runs for ``--seconds``.  With ``--trace 1`` the
window runs under the JAX profiler and the line carries the per-layer
metrics instead of the end-to-end ones.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (with ``--trace 1``
also ``breakdown``), and last ``checks``, each number compared beside its
limit; the same numbers close standard error.  With no TPU, or fewer
chips than the cell asks for, it exits with code 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def find(root: Path, kind: str, name: str) -> Path:
    """``<kind>/<name>`` under ``root``, else under the benchmark's own
    directory."""
    for base in dict.fromkeys((root, BENCH)):
        if (base / kind / name).is_file():
            return base / kind / name
    raise FileNotFoundError(f"no {kind} file {name!r} under {root}")


def load_json(root: Path, kind: str, name: str) -> dict:
    return json.loads(find(root, kind, f"{name}.json").read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def metric_readers(root: Path) -> dict:
    """Every per-layer metric reader under ``metrics/``, by name."""
    paths = {p.stem: p for base in dict.fromkeys((BENCH, root))
             for p in sorted((base / "metrics").glob("*.py"))
             if not p.stem.startswith("_")}
    return {k: load_module(p) for k, p in sorted(paths.items())}


class Window:
    """The measured window on the host clock."""

    def __init__(self):
        self.t0 = time.perf_counter()
        self.seconds = None

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0


class Context(SimpleNamespace):
    """What a traffic module gets: the cell, its configuration, the seed,
    the devices, host spans, the window and the trace."""

    def span(self, name: str, trace: bool = False):
        """Host-clock span ``name`` (summed over repeats); with ``trace``
        also a ``bench.<name>`` annotation on the profiler's clock."""
        @contextlib.contextmanager
        def cm():
            ann = contextlib.nullcontext()
            if trace and self.trace:
                import jax
                ann = jax.profiler.TraceAnnotation(f"bench.{name}")
            t0 = time.perf_counter()
            with ann:
                yield
            self.spans[name] = (self.spans.get(name, 0.0)
                                + time.perf_counter() - t0)
        return cm()

    def setup_done(self) -> float:
        self.setup_s = time.perf_counter() - T_START
        return self.setup_s

    @contextlib.contextmanager
    def window(self):
        import jax
        if self.trace:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir,
                                     profiler_options=opts)
        compiles0 = self.compiles[0]
        win = Window()
        try:
            if self.trace:
                with jax.profiler.TraceAnnotation("bench.window"):
                    yield win
            else:
                yield win
        finally:
            win.seconds = win.elapsed()
            if self.trace:
                jax.profiler.stop_trace()
            self.window_compiles = self.compiles[0] - compiles0

    def memory(self) -> list:
        """``[bytes in use, peak bytes in use]`` on each of the cell's
        chips, as the runtime reports them."""
        out = []
        for d in self.devices[:self.chips]:
            stats = d.memory_stats() or {}
            out.append([int(stats.get("bytes_in_use", 0)),
                        int(stats.get("peak_bytes_in_use", 0))])
        return out


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace here (default: a "
                         "temporary directory, removed after reading)")
    return ap.parse_args(argv)


def devices_for(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"no TPU visible (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, {len(devs)} visible")
    return devs


def execute(args, *, root: Path = BENCH, require_tpu: bool = True,
            interpret=False, cache: bool = True) -> dict:
    """One run; returns the result object (without printing it)."""
    workload = load_json(root, "workloads", args.workload)
    config = load_json(root, "configs", workload["config"])
    traffic = load_module(find(root, "traffic", f"{workload['traffic']}.py"))
    chips = int(workload["chips"])

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    if cache:
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = devices_for(chips, require_tpu)

    compiles = [0]

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_duration)

    own_dir = args.trace and not args.trace_dir
    trace_dir = (tempfile.mkdtemp(prefix="bench-trace-") if own_dir
                 else args.trace_dir)
    ctx = Context(workload=workload, config=config,
                  seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), trace_dir=trace_dir,
                  chips=chips, devices=devices, interpret=interpret,
                  spans={}, compiles=compiles, window_compiles=0)
    try:
        res = traffic.run(ctx)
        limits = workload["limits"]
        checks = {k: {"value": v, "limit": limits[k]}
                  for k, v in res["readings"].items()}
        correct = all(c["value"] <= c["limit"] for c in checks.values())
        dev = devices[0]
        device = {"platform": dev.platform, "kind": dev.device_kind,
                  "count": chips,
                  "memory_peak_bytes": res["memory_peak_bytes"]}
        out = {"correct": correct, "attempted": res["attempted"],
               "failed": res["failed"]}
        if args.trace:
            tr = load_module(BENCH / "trace.py")
            t = tr.load(tr.find(trace_dir))
            busy = tr.busy_ns(t)
            lo, hi = t.window()
            device["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
            device["window_s"] = (hi - lo) / 1e9
            readers = metric_readers(root)
            m = SimpleNamespace(trace=t, tracelib=tr, readers=readers,
                                work=load_module(BENCH / "work.py"),
                                facts=res["facts"], spans=ctx.spans,
                                e2e=res["e2e"], chips=chips,
                                peak=tr.peak(dev.device_kind)
                                if require_tpu else None)
            metrics = {}
            for name, mod in readers.items():
                v = mod.read(m)
                if v is not None:
                    metrics[name] = {"value": v, "unit": mod.UNIT}
            out["metrics"] = metrics
            out["device"] = device
            out["breakdown"] = tr.breakdown(t)
        else:
            out["metrics"] = {k: {"value": v, "unit": traffic.UNITS[k]}
                              for k, v in res["e2e"].items()}
            out["device"] = device
        out["checks"] = checks
        out["_facts"] = dict(res["facts"], spans=ctx.spans,
                             window_compiles=ctx.window_compiles)
        return out
    finally:
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]
    try:
        out = execute(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    facts = out.pop("_facts")
    print(f"bench: {args.workload} seed {args.seed}: {json.dumps(facts)}",
          file=sys.stderr)
    for k, c in out["checks"].items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
