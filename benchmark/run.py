"""Run one cell of BENCHMARK.json once and print its result line.

    python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device` and, traced, `breakdown`.
Without a TPU, or with fewer chips than the cell asks for, the run is an
error and prints no result; `--rehearse` (tests only) lifts that, runs the
cell's `rehearsal` sizes on whatever JAX finds, and names that device on
the line like any other.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmark")


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_manifest(held_out=False):
    """BENCHMARK.json; with `held_out`, also the cells that a benchmark PR
    took out of it and whose files stay: `held_out/<cell>.json` holds the
    entries (cell, end-to-end metric, per-layer metrics) to add back."""
    manifest = _load_json(ROOT, "BENCHMARK.json")
    if held_out:
        for path in sorted(glob.glob(os.path.join(HERE, "held_out", "*.json"))):
            extra = _load_json(path)
            for key in ("workloads", "end_to_end", "per_layer"):
                have = {e["name"] for e in manifest[key]}
                manifest[key] += [e for e in extra[key] if e["name"] not in have]
    return manifest


def _by_name(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


class Run:
    """What a driver is handed: the cell's files, the seed, the window."""

    def __init__(self, args, manifest):
        self.seed, self.seconds = args.seed, args.seconds
        self.trace, self.rehearse = bool(args.trace), args.rehearse
        self.cell = _by_name(manifest["workloads"], args.workload, "workload")
        self.workload = _load_json(HERE, "workloads", self.cell["name"] + ".json")
        entry = _by_name(manifest["configs"], self.cell["config"], "config")
        config_file = os.path.join(ROOT, entry["file"])
        if self.rehearse:
            self.workload.update(self.workload.get("rehearsal", {}))
            config_file = os.path.join(ROOT, self.workload["config_file"])
        self.config = _load_json(config_file)
        from benchmark import traffic

        self.mix = traffic.load_mix(self.cell["traffic"])
        self.mix.update(self.workload.get("mix", {}) if self.rehearse else {})
        self.window_s = None
        self.setup_s = None
        self.trace_summary = None
        self.peaks = None
        self._trace_dir = os.path.join(ROOT, ".benchmark_trace",
                                       self.cell["name"])

    @contextlib.contextmanager
    def window(self):
        """The measured window. The caller has synced the device."""
        import jax

        if self.trace:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        self.setup_s = t0 - _PROCESS_START
        try:
            yield
        finally:
            self.window_s = time.perf_counter() - t0
            if self.trace:
                jax.profiler.stop_trace()
                from benchmark import trace_reduce

                self.trace_summary = trace_reduce.summarize(
                    trace_reduce.load(self._trace_dir))
                shutil.rmtree(self._trace_dir, ignore_errors=True)


def tool_run(workload, seed, seconds, rehearse=False) -> Run:
    """A Run for the benchmark's own tools (read_limits, find_knee), which
    may name a held-out cell."""
    args = argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=0, rehearse=rehearse)
    return Run(args, load_manifest(held_out=True))


def _devices(run):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if not run.rehearse:
        if platform != "tpu":
            raise SystemExit(f"benchmark needs a TPU, JAX found {platform!r}")
        if len(devices) < run.cell["chips"]:
            raise SystemExit(f"cell needs {run.cell['chips']} chips, "
                             f"JAX found {len(devices)}")
        from benchmark import flops

        run.peaks = flops.peaks_for(devices[0].device_kind)
    return devices[:run.cell["chips"]]


def _layer_metric(name):
    """The reader of one per-layer metric: layer_metrics/<name>.py."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmark.layer_metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def _reports(metric, cell_name, cell_e2e):
    if "workloads" in metric:
        return cell_name in metric["workloads"]
    return metric.get("moves", metric["name"]) in cell_e2e


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--held-out", action="store_true",
                    help="also the cells in benchmark/held_out/ (never the driver's)")
    args = ap.parse_args(argv)

    manifest = load_manifest(args.held_out)
    run = Run(args, manifest)

    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    devices = _devices(run)
    driver = importlib.import_module("benchmark.drivers." + run.workload["driver"])
    out = driver.run(run, devices)
    # out: e2e {name: value}, attempted, failed, checks [(name, value,
    # limit)], obs (what the per-layer readers read), memory_peak_bytes

    # Each number compared beside its limit; failed requests are one.
    compared = [("failed_requests", out["failed"], 0), *out["checks"]]
    correct, said = True, []
    for name, value, limit in compared:
        ok = value <= limit  # a NaN is not within any limit
        correct = correct and ok
        said.append(f"check {name}: {value:.6g} (limit {limit:.6g}) "
                    f"{'ok' if ok else 'FAILED'}")
    print("\n".join(said))

    out["e2e"]["setup_s"] = run.setup_s
    cell_e2e = {m["name"] for m in manifest["end_to_end"]
                if m["name"] in out["e2e"]
                and ("workloads" not in m or run.cell["name"] in m["workloads"])}
    metrics = {}
    if run.trace:
        obs = dict(out["obs"], trace=run.trace_summary, peaks=run.peaks,
                   window_s=run.window_s,
                   memory_peak_bytes=out["memory_peak_bytes"])
        for m in manifest["per_layer"]:
            if not _reports(m, run.cell["name"], cell_e2e):
                continue
            value = _layer_metric(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in manifest["end_to_end"]:
            if m["name"] in cell_e2e:
                metrics[m["name"]] = {"value": float(out["e2e"][m["name"]]),
                                      "unit": m["unit"]}

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": bool(correct), "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics, "device": device}
    if run.trace and run.trace_summary is not None:
        device["busy_s"] = run.trace_summary["busy_s"]
        device["window_s"] = run.trace_summary["window_s"]
        line["breakdown"] = {
            "device_ops": run.trace_summary["top_ops"][:10],
            "idle_gaps": run.trace_summary["top_gaps"][:5]}
    if run.rehearse:
        line["rehearsal"] = True
    # The same numbers last in the line, and the last lines on standard error.
    line["compared"] = {
        name: {"value": float(value) if math.isfinite(value) else None,
               "limit": float(limit)} for name, value, limit in compared}
    sys.stdout.flush()
    print("\n".join(said), file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
