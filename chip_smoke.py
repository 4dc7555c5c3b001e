#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              one TPU chip, `base` width
    python chip_smoke.py --chips 4    a ragged server's mesh executable and the
                                      sharded trainer on a four-chip host

Drives the main path once through the entry points users type
(`python -m proteinbert_tpu pretrain | serve | map`), at the full width of
the `base` preset (6 blocks, local 512, global 512, 8 heads, key 64, 8,943
annotations, L=512, bf16, remat "convs"), weights random from a seed,
synthetic data from a seed, and checks what comes out. Needs no network.

ONE PROCESS PER CHIP: this script never imports JAX. Every phase is a child
process, started after the one before it has ended, so exactly one process
holds the chip at any time; the device on the last line is taken from the
first child's output. Children share the persistent compile cache
(utils/compat.configure_compile_cache: `JAX_COMPILATION_CACHE_DIR` if set,
else `.jax_cache/` in the checkout), so a later phase loads what an earlier
one compiled.

Output: one JSON object per phase on stdout (name, seconds, compile seconds,
what was checked), then, only if every phase passed, the last line

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed check ends the run non-zero before that line. Without an
accelerator the run fails in its first phase; `--platform cpu` is the sandbox
rehearsal (`tiny` width, Pallas in interpret mode), and its last line names
the cpu, never the chip. Child logs go to `chiprun_out/chip_smoke/`.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import re
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")
# The contract gives 1200 s, compilation included; phases share this budget.
DEADLINE_S = 1150.0
_T0 = time.monotonic()

AA = "ACDEFGHIKLMNPQRSTVWY"

# What each run is sized to. `real` is the chip; `cpu` the sandbox rehearsal.
SIZES = {
    "real": dict(
        preset="base", steps=24, ckpt_every=20,
        # `base` names a 16-device mesh (configs/config.py); one chip needs
        # the override, or `pretrain --preset base` refuses to start. The
        # 10,000-step warmup is cut so 20 steps move the loss.
        sets=("mesh.data=1", "optimizer.warmup_steps=8"),
        n_requests=36, n_map=300, map_max_len=480,
        kernel_shapes=(  # (name, C, G, H, key, L, B)
            ("base", 512, 512, 8, 64, 512, 8),
            ("paper", 128, 512, 4, 64, 512, 8),
        ),
        tiled=dict(C=1024, L=512, B=2),
        # The serving shape: the smallest row class of `base` ragged.
        served=dict(C=512, L=1024, B=64, S=8),
        multichip=dict(preset="base", long_preset="long", steps=3),
        # `--serve-mode ragged --mesh` on a host: the 256-row class, a
        # chip's quarter of it the smallest class one chip serves.
        served_mesh=dict(preset="base", rows=256, seq_len=1024, segments=8),
    ),
    "cpu": dict(
        preset="tiny", steps=12, ckpt_every=8,
        sets=("optimizer.warmup_steps=4",),
        n_requests=12, n_map=24, map_max_len=100,
        kernel_shapes=(
            ("base", 512, 512, 8, 64, 128, 2),
            ("paper", 128, 512, 4, 64, 128, 2),
        ),
        tiled=dict(C=1024, L=128, B=1),
        served=dict(C=128, L=256, B=2, S=4),
        multichip=dict(preset="tiny", long_preset="tiny", steps=2),
        served_mesh=dict(preset="tiny", rows=8, seq_len=128, segments=4),
    ),
}

# Phase 5's decision table: per (shape name, L, packed), the kernel family
# counters that MUST move and how. Anything else moving — a gate that
# quietly says "no" where a kernel was expected, or the other way round — is
# a failure. At `base` width the one-pass gate defers by its own VMEM
# pricing and the two-kernel Pallas composition runs; at the paper width the
# one-pass program itself runs.
_TWO_KERNEL_DENSE = {"onepass": "reference/unsupported_shape",
                     "fused": "pallas/dense", "attention": "pallas/dense"}
_TWO_KERNEL_PACKED = {"onepass": "reference/segments",
                      "fused": "pallas/packed", "attention": "pallas/packed"}
KERNEL_DECISIONS = {
    ("base", 512, False): _TWO_KERNEL_DENSE,
    ("base", 512, True): _TWO_KERNEL_PACKED,
    ("paper", 512, False): {"onepass": "pallas/dense"},
    ("paper", 512, True): {"onepass": "pallas/packed"},
    # The rehearsal's shorter rows (interpret mode is slow): same widths,
    # L=128 — where C=512 still defers and C=128 still fuses.
    ("base", 128, False): _TWO_KERNEL_DENSE,
    ("base", 128, True): _TWO_KERNEL_PACKED,
    ("paper", 128, False): {"onepass": "pallas/dense"},
    ("paper", 128, True): {"onepass": "pallas/packed"},
}
# bf16 block outputs are O(1) after LayerNorm; the Pallas and XLA paths
# differ by accumulation order and bf16 rounding of intermediates.
KERNEL_TOL = 0.06
# The same sequence embedded by the bucketed (padded) and the ragged (packed)
# server: two executables, bf16, one answer.
SERVE_MODE_TOL = 0.06
# Per-step loss agreement of a sharded run with the one-device run: the same
# init and batches, reductions in another order, bf16 activations.
MULTICHIP_LOSS_TOL = 2e-2


class SmokeFailure(SystemExit):
    """A failed check. Exits non-zero; nothing catches it on the way out."""

    def __init__(self, phase: str, why: str):
        super().__init__(f"chip_smoke: phase {phase!r} FAILED: {why}")


def check(cond, phase: str, why: str) -> None:
    if not cond:
        raise SmokeFailure(phase, why)


def remaining() -> float:
    return DEADLINE_S - (time.monotonic() - _T0)


# --------------------------------------------------------------- children

def child_env(opts) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_LOG_COMPILES"] = "1"  # JAX's own switch: compile lines → log
    env.setdefault("TPU_LOG_DIR", "disabled")
    if opts.platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    return env


def cli_cmd(opts, *args) -> list:
    pre = ["--platform", "cpu"] if opts.platform == "cpu" else []
    return [sys.executable, "-m", "proteinbert_tpu", *pre, *args]


def fn_cmd(name: str, payload: dict) -> list:
    """A phase that lives in this file (device, kernels, multichip), run as
    a child like every other phase."""
    code = (f"import chip_smoke; "
            f"chip_smoke.CHILD_PHASES[{name!r}]({json.dumps(payload)!r})")
    return [sys.executable, "-c", code]


def log_path(name: str) -> str:
    os.makedirs(LOG_DIR, exist_ok=True)
    return os.path.join(LOG_DIR, f"{name}.log")


def tail(path: str, n: int = 40) -> str:
    try:
        with open(path, errors="replace") as f:
            lines = [l for l in f.read().splitlines()
                     if not any(noise in l for noise in (
                         "Compiling ", "Finished ", "cpu_aot_loader",
                         "compilation cache"))]
    except OSError as e:
        return f"<no log: {e}>"
    return "\n".join(lines[-n:])


def run_child(phase: str, name: str, cmd: list, opts,
              cap: float = 900.0) -> str:
    """Run one child to its end; returns its stdout. stderr → the log."""
    timeout = min(cap, remaining())
    check(timeout > 5, phase, "out of time before starting " + name)
    log = log_path(name)
    with open(log, "w") as lf:
        try:
            p = subprocess.run(cmd, cwd=REPO, env=child_env(opts),
                               stdout=subprocess.PIPE, stderr=lf,
                               text=True, timeout=timeout)
        except subprocess.TimeoutExpired:
            raise SmokeFailure(phase, f"{name} exceeded {timeout:.0f}s; "
                                      f"log tail:\n{tail(log)}")
    with open(log, "a") as lf:
        lf.write("\n----- stdout -----\n" + p.stdout)
    check(p.returncode == 0, phase,
          f"{name} exited {p.returncode}; log tail:\n{tail(log)}")
    return p.stdout


def last_json(phase: str, stdout: str) -> dict:
    for line in reversed(stdout.strip().splitlines()):
        if line.startswith("{"):
            return json.loads(line)
    raise SmokeFailure(phase, f"child printed no JSON line: {stdout[-400:]!r}")


_MSG = re.compile(r"^WARNING:(?:[\d\- :,]+:)?jax\._src\.[\w.]+:(?:\d+: )?(.*)$")
_FIN = re.compile(r"Finished XLA compilation of (\S+) in ([\d.]+) sec")
_HIT = re.compile(r"Persistent compilation cache hit for '([^']+)'")


def compile_stats(*log_names: str) -> dict:
    """Reduce a child's JAX_LOG_COMPILES lines: programs built or loaded,
    seconds spent there, and how many came from the persistent cache.
    (A logging set-up with two handlers prints each message twice in a
    row; adjacent repeats are one event.)"""
    n, secs, hits, per = 0, 0.0, 0, {}
    for name in log_names:
        prev = None
        with open(log_path(name), errors="replace") as f:
            for line in f:
                m = _MSG.match(line.rstrip("\n"))
                if not m:
                    continue
                msg = m.group(1)
                if msg == prev:
                    continue
                prev = msg
                fin = _FIN.search(msg)
                if fin:
                    n += 1
                    secs += float(fin.group(2))
                    per[fin.group(1)] = per.get(fin.group(1), 0) + 1
                elif _HIT.search(msg):
                    hits += 1
    return {"programs": n, "compile_seconds": round(secs, 2),
            "cache_hits": hits, "per_program": per}


def emit(phase: str, t0: float, stats: dict, checked: dict) -> None:
    print(json.dumps({
        "phase": phase,
        "seconds": round(time.monotonic() - t0, 2),
        "compile_seconds": stats.get("compile_seconds"),
        "programs": stats.get("programs"),
        "cache_hits": stats.get("cache_hits"),
        "checked": checked,
    }), flush=True)


# ------------------------------------------------------------ phase: device

def phase_device(opts) -> dict:
    t0 = time.monotonic()
    out = last_json("device", run_child(
        "device", "device",
        fn_cmd("device", {"cpu_devices": opts.chips
                          if opts.platform == "cpu" else 0}), opts, cap=300))
    want = "cpu" if opts.platform == "cpu" else "tpu"
    check(out["platform"] == want, "device",
          f"JAX found platform {out['platform']!r}, this run needs "
          f"{want!r} (no accelerator → no result; the sandbox rehearsal "
          "is --platform cpu)")
    check(out["count"] == opts.chips, "device",
          f"run needs {opts.chips} device(s), JAX reports {out['count']}")
    if want == "tpu":
        check(isinstance(out["bytes_limit"], int) and out["bytes_limit"] > 0,
              "device", f"no memory_stats bytes_limit: {out['bytes_limit']}")
    emit("device", t0, compile_stats("device"), out)
    return out


def _child_device(payload: str) -> None:
    args = json.loads(payload)
    import jax

    from proteinbert_tpu.utils.compat import (
        configure_compile_cache, request_cpu_devices,
    )

    if args["cpu_devices"]:
        request_cpu_devices(args["cpu_devices"])
    cache_dir = configure_compile_cache()
    devices = jax.devices()
    stats = devices[0].memory_stats() or {}

    from jax._src import hardware_utils

    from proteinbert_tpu.native import native_available
    from proteinbert_tpu.train.metrics import peak_flops_per_chip

    print(json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "bytes_limit": stats.get("bytes_limit"),
        # Raises for a device_kind the table does not know.
        "peak_flops_per_chip": peak_flops_per_chip(devices[0]),
        "pci_tpu_chips":
            hardware_utils.num_available_tpu_chips_and_device_id()[0],
        "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        # Host-side C++ helpers, built on first use from the committed
        # .cpp files; False = the Python fallback ran.
        "native": {n: native_available(n)
                   for n in ("tokenizer", "fasta_index")},
    }))


# ---------------------------------------------------------- phase: pretrain

def _pretrain_cmd(opts, size, run_dir, tag, work):
    sets = [*size["sets"], f"checkpoint.every_steps={size['ckpt_every']}",
            "train.log_every=1"]
    cmd = cli_cmd(opts, "pretrain", "--preset", size["preset"],
                  "--max-steps", str(size["steps"]),
                  "--checkpoint-dir", run_dir,
                  "--events-jsonl", os.path.join(work, f"{tag}.events.jsonl"),
                  "--metrics-jsonl",
                  os.path.join(work, f"{tag}.metrics.jsonl"))
    for s in sets:
        cmd += ["--set", s]
    return cmd


def _read_jsonl(path):
    with open(path) as f:
        return [json.loads(l) for l in f if l.strip()]


def phase_pretrain(opts, size, work, device) -> str:
    t0 = time.monotonic()
    run_dir = os.path.join(work, "run")
    n, every = size["steps"], size["ckpt_every"]
    run_child("pretrain", "pretrain",
              _pretrain_cmd(opts, size, run_dir, "pretrain", work), opts)
    rows = [r for r in _read_jsonl(os.path.join(work, "pretrain.metrics.jsonl"))
            if "loss" in r]
    losses = {r["step"]: r["loss"] for r in rows}
    check(sorted(losses) == list(range(1, n + 1)), "pretrain",
          f"expected a loss for steps 1..{n}, got {sorted(losses)}")
    check(all(v == v and abs(v) != float("inf") for v in losses.values()),
          "pretrain", f"non-finite loss: {losses}")
    last5 = sum(losses[s] for s in range(n - 4, n + 1)) / 5
    check(last5 < losses[1], "pretrain",
          f"loss did not fall: first {losses[1]:.4f}, mean of last five "
          f"{last5:.4f}")
    events = _read_jsonl(os.path.join(work, "pretrain.events.jsonl"))
    end = events[-1]
    check(end["event"] == "run_end" and end["outcome"] == "completed"
          and end["step"] == n, "pretrain", f"run_end was {end}")
    check(os.path.isfile(os.path.join(run_dir, "config.json")), "pretrain",
          "no config.json beside the checkpoints")
    for step in (every, n):
        check(os.path.isdir(os.path.join(run_dir, str(step))), "pretrain",
              f"no checkpoint directory for step {step} in {run_dir}")
    stats = compile_stats("pretrain")
    check(stats["per_program"].get("jit(train_step)") == 1, "pretrain",
          "train_step compiled "
          f"{stats['per_program'].get('jit(train_step)')} time(s) inside "
          f"the {n}-step window, expected exactly 1")
    log = open(log_path("pretrain"), errors="replace").read()
    rate = re.search(r"done: (\d+) residues/s/chip, MFU ([\d.]+) on (.+)", log)
    check(rate is not None, "pretrain", "no 'done: … res/s, MFU … on "
          "<device>' line in the trainer's log")
    check(device["kind"] in rate.group(3), "pretrain",
          f"rates logged beside {rate.group(3)!r}, not the device kind "
          f"{device['kind']!r}")
    data_cfg = json.load(open(os.path.join(run_dir, "config.json")))["data"]
    emit("pretrain", t0, stats, {
        "steps": n, "batch": [data_cfg["batch_size"], data_cfg["seq_len"]],
        "loss_first": losses[1],
        "loss_last5_mean": round(last5, 4),
        "residues_per_sec_per_chip": int(rate.group(1)),
        "mfu": float(rate.group(2)), "measured_on": rate.group(3).strip(),
        "train_step_compiles": 1,
        "checkpoints": [every, n],
    })

    # Resume: a copy of the run cut back to its step-`every` checkpoint must
    # retrace the uninterrupted run's remaining steps exactly (same chip,
    # same executable, restored state + RNG + data position).
    t1 = time.monotonic()
    resumed = os.path.join(work, "run_resumed")
    shutil.copytree(run_dir, resumed)
    shutil.rmtree(os.path.join(resumed, str(n)))
    run_child("pretrain", "resume",
              _pretrain_cmd(opts, size, resumed, "resume", work), opts)
    again = {r["step"]: r["loss"] for r in
             _read_jsonl(os.path.join(work, "resume.metrics.jsonl"))
             if "loss" in r}
    tail_steps = list(range(every + 1, n + 1))
    check(sorted(again) == tail_steps, "pretrain",
          f"resume ran steps {sorted(again)}, expected {tail_steps}")
    check(all(again[s] == losses[s] for s in tail_steps), "pretrain",
          "resumed losses differ from the uninterrupted run: "
          f"{[(s, losses[s], again[s]) for s in tail_steps]}")
    ev = _read_jsonl(os.path.join(work, "resume.events.jsonl"))
    check(ev[0]["event"] == "run_start" and ev[0].get("resumed") is True
          and ev[-1]["event"] == "run_end" and ev[-1]["step"] == n,
          "pretrain", f"resume events: {ev[0]} … {ev[-1]}")
    rstats = compile_stats("resume")
    emit("resume", t1, rstats, {
        "from_step": every, "to_step": n, "losses_equal_uninterrupted": True,
        # The same train_step in a fresh process: loaded, not rebuilt.
        "train_step_from_cache": rstats["cache_hits"] > 0,
    })
    check(rstats["cache_hits"] > 0, "pretrain",
          "the resumed process compiled everything again: no persistent "
          "cache hit")
    return run_dir


# ------------------------------------------------------------- phase: serve

def _http(method, url, body=None, timeout=120):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(url, data=data, method=method,
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _finite(xs) -> bool:
    return all(isinstance(x, (int, float)) and x == x
               and abs(x) != float("inf") for x in xs)


def phase_serve(opts, size, work, run_dir, mode: str) -> dict:
    phase = f"serve_{mode}"
    t0 = time.monotonic()
    cfg = json.load(open(os.path.join(run_dir, "config.json")))
    window = cfg["data"]["seq_len"]
    C, G = cfg["model"]["local_dim"], cfg["model"]["global_dim"]
    port_file = os.path.join(work, f"{phase}.port")
    events = os.path.join(work, f"{phase}.events.jsonl")
    cmd = cli_cmd(opts, "serve", "--pretrained", run_dir, "--port", "0",
                  "--port-file", port_file, "--events-jsonl", events,
                  "--serve-mode", mode)
    log = log_path(phase)
    lf = open(log, "w")
    proc = subprocess.Popen(cmd, cwd=REPO, env=child_env(opts),
                            stdout=lf, stderr=lf)
    try:
        boot_cap = min(600.0, remaining())
        while not (os.path.exists(port_file)
                   and open(port_file).read().strip()):
            check(proc.poll() is None, phase,
                  f"server died during boot; log tail:\n{tail(log)}")
            check(time.monotonic() - t0 < boot_cap, phase,
                  f"server not listening after {boot_cap:.0f}s; log "
                  f"tail:\n{tail(log)}")
            time.sleep(0.2)
        boot_s = time.monotonic() - t0
        base = f"http://127.0.0.1:{open(port_file).read().strip()}"

        st, body, _ = _http("GET", base + "/healthz")
        health = json.loads(body)
        check(st == 200 and health["ok"] and health["mode"] == mode, phase,
              f"/healthz: {st} {body[:300]!r}")
        st, body, _ = _http("GET", base + "/metrics")
        check(st == 200 and b"serve_" in body, phase,
              f"/metrics: {st} {body[:200]!r}")

        rng = random.Random(20260926)
        accepted = 0  # requests the server admitted (gave a request id)
        by_status: dict = {}

        def post(path, payload, want=200):
            nonlocal accepted
            st, body, hdr = _http("POST", base + path, payload)
            by_status[st] = by_status.get(st, 0) + 1
            check(st == want, phase, f"POST {path} → {st}, wanted {want}: "
                                     f"{body[:300]!r}")
            if want == 200:
                rid = {k.lower(): v for k, v in hdr.items()}.get(
                    "x-pbt-request-id")
                check(bool(rid), phase, f"POST {path}: no X-PBT-Request-Id")
                accepted += 1
            return json.loads(body)

        def seq(n):
            return "".join(rng.choice(AA) for _ in range(n))

        for i in range(size["n_requests"]):
            s = seq(rng.randint(8, window - 2))
            kind = i % 3
            if kind == 0:
                r = post("/v1/embed", {"seq": s})
                check(len(r["global"]) == G and len(r["local_mean"]) == C
                      and _finite(r["global"]) and _finite(r["local_mean"]),
                      phase, f"/v1/embed shape/finiteness: {len(r['global'])}"
                             f", {len(r['local_mean'])}")
            elif kind == 1:
                r = post("/v1/predict_go", {"seq": s, "top_k": 5})
                check(len(r["top"]) == 5 and all(
                    0.0 <= p <= 1.0 for _, p in r["top"]), phase,
                    f"/v1/predict_go: {r}")
            else:
                m = list(s)
                for j in rng.sample(range(len(m)), max(1, len(m) // 10)):
                    m[j] = "?"
                r = post("/v1/predict_residues", {"seq": "".join(m)})
                check(len(r["filled"]) == len(m) and "?" not in r["filled"],
                      phase, f"/v1/predict_residues: {r}")
        # One longer than the window (served truncated and counted), one
        # malformed (a typed 400 at the door: never admitted, so it is
        # outside the accepted == sealed account).
        post("/v1/embed", {"seq": seq(window + 200)})
        bad = post("/v1/embed", {"seq": 42}, want=400)
        check(bad.get("type") == "bad_request", phase, f"malformed: {bad}")
        # A repeat returns the identical vector (the result cache), and the
        # probe sequence is what the two serve modes are compared on.
        probe = "".join(AA[(7 * i) % 20] for i in range(min(97, window - 2)))
        first = post("/v1/embed", {"seq": probe})
        second = post("/v1/embed", {"seq": probe})
        check(first == second, phase, "a repeated sequence returned a "
                                      "different vector")

        # Candidate pricing reads the device's memory budget
        # (serve/dispatch._device_hbm_bytes): a number on the chip.
        st, body, _ = _http("POST", base + "/v1/rollout/load",
                            {"source": run_dir}, timeout=300)
        load = json.loads(body)
        check(st == 200, phase, f"/v1/rollout/load → {st}: {body[:300]!r}")
        budget = load.get("hbm_budget_bytes")
        if opts.platform != "cpu":
            check(isinstance(budget, int) and budget > 0, phase,
                  f"candidate pricing budget is {budget!r} on the chip")
        st, body, _ = _http("POST", base + "/v1/rollout/unload", {})
        check(st == 200, phase, f"/v1/rollout/unload → {st}")

        st, body, _ = _http("GET", base + "/healthz")
        stats = json.loads(body)["stats"]
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=min(120.0, max(remaining(), 5)))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(phase, "server did not drain within 120 s of "
                                      f"SIGTERM; log tail:\n{tail(log)}")
        check(rc == 0, phase, f"server exited {rc} after SIGTERM; log "
                              f"tail:\n{tail(log)}")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        lf.close()

    ev = _read_jsonl(events)
    end = ev[-1]
    check(end["event"] == "serve_end" and end["outcome"] == "drained", phase,
          f"events stream ends {end.get('event')}"
          f"{{outcome={end.get('outcome')}}}")
    sealed = sum(1 for e in ev if e["event"] == "serve_request")
    es = end["stats"]
    accounted = (es["completed"] + es["cache_hit_returns"]
                 + sum(es["rejected"].values()))
    check(accepted == sealed == accounted, phase,
          f"accepted {accepted}, sealed {sealed} serve_request events, "
          f"accounted {accounted} in serve_end")
    check(es["truncated"] >= 1 and es["cache_hit_returns"] >= 1, phase,
          f"truncated={es['truncated']} cache_hits="
          f"{es['cache_hit_returns']}")
    cstats = compile_stats(phase)
    emit(phase, t0, cstats, {
        "mode": mode, "boot_seconds": round(boot_s, 2),
        "warm_executables": es["executables"],
        "warmup_seconds": es["warmup_seconds"],
        "accepted": accepted, "sealed": sealed, "by_status": by_status,
        "truncated": es["truncated"],
        "cache_hit_returns": es["cache_hit_returns"],
        "batches": es["batches"],
        "hbm_budget_bytes": budget,
        "p50_s": es["latency"]["p50_s"], "p99_s": es["latency"]["p99_s"],
        "fused_path": stats.get("fused_path"),
        "drained": True,
    })
    return {"probe": first}


# --------------------------------------------------------------- phase: map

def phase_map(opts, size, work, run_dir) -> None:
    t0 = time.monotonic()
    rng = random.Random(7)
    fasta = os.path.join(work, "corpus.fasta")
    with open(fasta, "w") as f:
        for i in range(size["n_map"]):
            n = min(size["map_max_len"],
                    max(12, int(rng.lognormvariate(4.6, 0.6))))
            f.write(f">smoke{i:04d}\n"
                    + "".join(rng.choice(AA) for _ in range(n)) + "\n")
    store = os.path.join(work, "store")
    events = os.path.join(work, "map.events.jsonl")
    run_child("map", "map", cli_cmd(
        opts, "map", "--pretrained", run_dir, "--store", store,
        "--fasta", fasta, "--num-shards", "2", "--events-jsonl", events),
        opts)
    out = run_child("map", "map_verify",
                    cli_cmd(opts, "map", "--store", store, "--verify"), opts,
                    cap=120)
    report = last_json("map", out)
    check(report["ok"] and report["complete"]
          and report["embedded"] == size["n_map"], "map",
          f"verify report: {report}")
    emit("map", t0, compile_stats("map"), {
        "sequences": size["n_map"], "embedded": report["embedded"],
        "blocks_checked": report["blocks_checked"],
        "quarantined": report["quarantined"], "verify_exit": 0,
    })


# ----------------------------------------------------------- phase: kernels

def phase_kernels(opts, size) -> None:
    t0 = time.monotonic()
    out = last_json("kernels", run_child("kernels", "kernels", fn_cmd(
        "kernels", {"shapes": size["kernel_shapes"], "tiled": size["tiled"],
                    "served": size["served"], "tol": KERNEL_TOL}), opts))
    for row in out["rows"]:
        key = (row["shape"], row["L"], row["packed"])
        check(key in KERNEL_DECISIONS, "kernels", f"no decision row {key}")
        check(row["decisions"] == KERNEL_DECISIONS[key], "kernels",
              f"{key}: kernel path counters moved {row['decisions']}, "
              f"expected {KERNEL_DECISIONS[key]}")
        check(row["max_err"] <= KERNEL_TOL and row["grad_err"] <= KERNEL_TOL,
              "kernels", f"{key}: Pallas vs XLA forward {row['max_err']:.4f}"
              f" / gradient {row['grad_err']:.4f} beyond {KERNEL_TOL}")
        if out["platform"] == "tpu":
            check(row["tpu_custom_calls"] >= 1, "kernels",
                  f"{key}: no tpu_custom_call in the compiled text")
    check(out["tiled"]["max_err"] <= KERNEL_TOL, "kernels",
          f"C=1024 channel-tiled kernels: {out['tiled']}")
    served = out["served"]
    check(served["kernel_vs_xla"]["max"] <= KERNEL_TOL
          and served["kernel_vs_f32"]["max"] <= KERNEL_TOL, "kernels",
          f"the served packed local track: {served}")
    check(served["decision"] == "pallas/packed", "kernels",
          f"the served shape took {served['decision']}, not the kernel")
    emit("kernels", t0, compile_stats("kernels"), out)


def _child_kernels(payload: str) -> None:
    args = json.loads(payload)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    from proteinbert_tpu import kernels as K
    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.models import proteinbert

    platform = jax.devices()[0].platform
    families = {"fused": K.PATH_TOTAL, "attention": K.ATTN_PATH_TOTAL,
                "onepass": K.ONEPASS_PATH_TOTAL}
    def err(a, b):
        """Largest deviation, relative to the reference's own scale."""
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        assert np.isfinite(a).all() and np.isfinite(b).all()
        return float(np.max(np.abs(a - b))
                     / max(1.0, float(np.max(np.abs(b)))))

    rows = []
    for name, C, G, H, kd, L, B in args["shapes"]:
        cfg_x = ModelConfig(local_dim=C, global_dim=G, num_heads=H,
                            key_dim=kd, num_blocks=1, dtype="bfloat16")
        cfg_p = ModelConfig(local_dim=C, global_dim=G, num_heads=H,
                            key_dim=kd, num_blocks=1, dtype="bfloat16",
                            use_pallas=True)
        k0, k1, k2, k3 = jax.random.split(jax.random.PRNGKey(C + L), 4)
        block = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a,
            proteinbert.block_init(k0, cfg_x))
        local = jax.random.normal(k1, (B, L, C), jnp.bfloat16)
        S = 8
        lengths = np.asarray(
            jax.random.randint(k3, (B,), L // 2, L - 8))
        for packed in (False, True):
            if packed:
                seg = np.zeros((B, L), np.int32)
                for b in range(B):  # 3 proteins per row, then pad
                    cuts = [0, lengths[b] // 3, 2 * lengths[b] // 3,
                            lengths[b]]
                    for s in range(3):
                        seg[b, cuts[s]:cuts[s + 1]] = s + 1
                seg = jnp.asarray(seg)
                glob = jax.random.normal(k2, (B, S, G), jnp.bfloat16)
                mask = seg > 0
            else:
                seg = None
                glob = jax.random.normal(k2, (B, G), jnp.bfloat16)
                mask = jnp.arange(L)[None, :] < jnp.asarray(lengths)[:, None]

            def loss(cfg):
                def f(p, x, g):
                    lo, go = proteinbert.block_apply(
                        p, x, g, mask, cfg, segment_ids=seg)
                    val = (jnp.mean(lo.astype(jnp.float32) ** 2)
                           + jnp.mean(go.astype(jnp.float32) ** 2))
                    return val, (lo, go)
                return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                                  has_aux=True))

            before = {n: dict(t) for n, t in families.items()}
            fp = loss(cfg_p)
            compiled = fp.lower(block, local, glob).compile()
            (_, (lo_p, go_p)), grads_p = compiled(block, local, glob)
            moved = {}
            for n, t in families.items():
                delta = {f"{p}/{r}": c - before[n].get((p, r), 0)
                         for (p, r), c in t.items()
                         if c - before[n].get((p, r), 0) > 0}
                if delta:
                    # One decision per family per trace is the contract;
                    # two different ones would be a split dispatch.
                    moved[n] = (next(iter(delta)) if len(delta) == 1
                                else sorted(delta))
            (_, (lo_x, go_x)), grads_x = loss(cfg_x)(block, local, glob)

            m = np.asarray(mask)
            fwd = max(err(np.asarray(lo_p, np.float32) * m[..., None],
                          np.asarray(lo_x, np.float32) * m[..., None]),
                      err(go_p, go_x))
            gerr = max(err(a, b) for a, b in zip(
                jax.tree.leaves(grads_p), jax.tree.leaves(grads_x)))
            rows.append({
                "shape": name, "C": C, "G": G, "H": H, "L": L, "B": B,
                "packed": packed, "decisions": moved,
                "max_err": round(fwd, 5), "grad_err": round(gerr, 5),
                "tpu_custom_calls": compiled.as_text().count(
                    "tpu_custom_call"),
            })

    # The channel-tiled kernels (C > 512, the Large width): the weights-
    # resident grid order pins its output block during non-finish sweeps
    # and relies on Mosaic writing a block back only when its index
    # changes — which interpret mode cannot show, the chip can.
    from proteinbert_tpu.kernels.fused_block import _plan_tiled
    from proteinbert_tpu.ops.attention import (
        global_attention_init, packed_global_attention_apply,
    )

    t = args["tiled"]
    C, L, B = t["C"], t["L"], t["B"]
    tc, tile = _plan_tiled(C, L, "bfloat16", resident=True)
    assert tc > 0, f"no weights-resident plan at C={C}/L={L}"
    interp = K.pallas_interpret()
    cfg = ModelConfig(local_dim=C, global_dim=64, key_dim=16, num_heads=4,
                      num_blocks=1, num_annotations=32, dtype="bfloat16")
    kp, kx, kb = jax.random.split(jax.random.PRNGKey(0), 3)
    block = proteinbert.block_init(kp, cfg)
    params = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                    "local_dense", "local_ln2")}
    x = jax.random.normal(kx, (B, L, C), jnp.bfloat16)
    bcast = jax.random.normal(kb, (B, C), jnp.bfloat16)

    e_dense = err(
        K.fused_local_track(params, x, bcast, 1, 5, interp),
        K.local_track_reference(params, x, bcast, 1, 5))
    S = 4
    seg = np.zeros((B, L), np.int32)
    seg[:, : L // 2] = 1
    seg[:, L // 2: L - 30] = 2
    seg = jnp.asarray(seg)
    bc_seg = jax.random.normal(jax.random.PRNGKey(7), (B, S, C), jnp.bfloat16)
    e_seg = err(
        K.fused_local_track_segments(params, x, bc_seg, seg, 1, 5, interp),
        K.local_track_segment_reference(
            params, x, K.gather_segment_broadcast(bc_seg, seg), seg, 1, 5))
    aparams = global_attention_init(jax.random.PRNGKey(8), C, 64, 16, 4)
    gseg = jax.random.normal(jax.random.PRNGKey(9), (B, S, 64), jnp.bfloat16)
    e_attn = err(
        K.fused_packed_attention(aparams, x, gseg, seg, interpret=interp),
        packed_global_attention_apply(aparams, x, gseg, seg))
    served = _served_track(args["served"], interp, platform)
    print(json.dumps({
        "platform": platform, "interpret": interp, "tolerance": args["tol"],
        "rows": rows, "served": served,
        "tiled": {"C": C, "L": L, "plan": [tc, tile],
                  "dense_err": round(e_dense, 5),
                  "segments_err": round(e_seg, 5),
                  "attention_err": round(e_attn, 5),
                  "max_err": round(max(e_dense, e_seg, e_attn), 5)},
    }))


def _served_track(shape: dict, interp: bool, platform: str) -> dict:
    """The local track of a FORWARD-ONLY packed batch at the serving shape
    (ISSUE 42): the segment kernel against the XLA composition it replaces
    there (`local_track_segment_reference`, bfloat16) and both against the
    same track in float32 over the same bfloat16-rounded weights and input.
    Rows as the ragged server packs them: spans one after another with a
    boundary ON a tile edge and one inside the wide conv's halo of it, a
    pad tail, and the last row all pad. Gaps over the real positions: norm
    of the difference over the reference's norm (`rel`), and the largest
    deviation over the reference's largest value (`max`). On the chip also
    the mean time of a call, each path jitted alone."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from proteinbert_tpu import kernels as K
    from proteinbert_tpu.configs import ModelConfig
    from proteinbert_tpu.kernels.fused_block import _segment_tile
    from proteinbert_tpu.models import proteinbert

    C, L, B, S = shape["C"], shape["L"], shape["B"], shape["S"]
    cfg = ModelConfig(local_dim=C, global_dim=64, key_dim=16, num_heads=4,
                      num_blocks=1, num_annotations=32, dtype="bfloat16")
    kp, kx, kb = jax.random.split(jax.random.PRNGKey(42), 3)
    block = proteinbert.block_init(kp, cfg)
    params = {k: block[k] for k in ("narrow_conv", "wide_conv", "local_ln1",
                                    "local_dense", "local_ln2")}
    rounded = jax.tree.map(
        lambda a: (a.astype(jnp.bfloat16).astype(jnp.float32)
                   if a.ndim >= 2 else a), params)
    x = jax.random.normal(kx, (B, L, C), jnp.bfloat16)
    bc = jax.random.normal(kb, (B, S, C), jnp.bfloat16)
    tile = _segment_tile(C, L, S, "bfloat16", 9, 9, 20)
    assert tile > 0, f"no plan at the served shape {shape}"
    rng = np.random.default_rng(42)
    seg = np.zeros((B, L), np.int32)
    for b in range(B - 1):
        cuts = [0, tile, tile + 7] + sorted(
            rng.choice(np.arange(tile + 40, L - 24), S - 3, replace=False))
        cuts.append(L - int(rng.integers(0, 24)))
        for i in range(S):
            seg[b, cuts[i]:cuts[i + 1]] = i + 1
    seg = jnp.asarray(seg)

    def xla(p, xx, bb, ss):
        return K.local_track_segment_reference(
            p, xx, K.gather_segment_broadcast(bb, ss), ss, 1, 5)

    def f32(p, xx, bb, ss):
        with jax.default_matmul_precision("highest"):
            return xla(p, xx.astype(jnp.float32), bb.astype(jnp.float32),
                       ss)

    before = dict(K.PATH_TOTAL)
    kernel = jax.jit(lambda p, xx, bb, ss: K.fused_local_track_segments(
        p, xx, bb, ss, 1, 5, interp))
    got_k = kernel(params, x, bc, seg)
    moved = sorted(f"{p}/{r}" for (p, r), c in K.PATH_TOTAL.items()
                   if c != before.get((p, r), 0))
    xla_j = jax.jit(xla)
    got_x = xla_j(params, x, bc, seg)
    want = jax.jit(f32)(rounded, x, bc, seg)
    real = np.asarray(seg) > 0

    def gap(a, b):
        a = np.asarray(a, np.float32)[real]
        b = np.asarray(b, np.float32)[real]
        assert np.isfinite(a).all() and np.isfinite(b).all()
        return {"rel": round(float(np.linalg.norm(a - b)
                                   / np.linalg.norm(b)), 6),
                "max": round(float(np.max(np.abs(a - b))
                                   / max(1.0, float(np.max(np.abs(b))))), 5)}

    out = {"shape": shape, "tile": tile,
           "decision": moved[0] if len(moved) == 1 else moved,
           "kernel_vs_xla": gap(got_k, got_x),
           "kernel_vs_f32": gap(got_k, want),
           "xla_vs_f32": gap(got_x, want)}
    if platform == "tpu":
        for name, fn in (("kernel_ms", kernel), ("xla_ms", xla_j)):
            fn(params, x, bc, seg).block_until_ready()
            t0 = time.perf_counter()
            for _ in range(20):
                y = fn(params, x, bc, seg)
            y.block_until_ready()
            out[name] = round((time.perf_counter() - t0) / 20 * 1e3, 3)
    return out


# ------------------------------------------------------- phase: served_mesh

def phase_served_mesh(opts, size) -> None:
    t0 = time.monotonic()
    out = last_json("served_mesh", run_child(
        "served_mesh", "served_mesh", fn_cmd("served_mesh", {
            **size["served_mesh"],
            "cpu_devices": 4 if opts.platform == "cpu" else 0}), opts,
        cap=600))
    check(out["worst_gap"] <= SERVE_MODE_TOL, "served_mesh",
          f"a chip's quarter of the batch differs from the same rows on "
          f"one chip by {out['worst_gap']}: {out}")
    check(out["devices_holding_outputs"] == 4 and not out["collectives"],
          "served_mesh", f"not four replicas on their own rows: {out}")
    if out["platform"] == "tpu":
        check(out["fused_path"] == {"pallas/packed": 2}
              and out["tpu_custom_calls"] == 1, "served_mesh",
              f"the mesh's executable does not run the kernel: {out}")
    emit("served_mesh", t0, compile_stats("served_mesh"), out)


def _child_served_mesh(payload: str) -> None:
    """`--serve-mode ragged --mesh` (ISSUE 42): a ragged dispatcher over
    data=2 x fsdp=2 runs one packed batch of `rows` through its `embed`
    executable (`parallel/sharding.on_each_replica`: every chip the
    one-chip program on its quarter), against the first quarter of the
    same batch through `inference._packed_encode_batch` on one chip. The
    compiled text has no collective; on the chip it has the segment
    kernel, and both calls are timed: four chips take as long over `rows`
    as one over a quarter."""
    args = json.loads(payload)
    import jax
    import numpy as np

    from proteinbert_tpu.utils.compat import (
        configure_compile_cache, request_cpu_devices,
    )

    if args["cpu_devices"]:
        request_cpu_devices(args["cpu_devices"])
    configure_compile_cache()
    import dataclasses

    from proteinbert_tpu import inference, kernels as K
    from proteinbert_tpu.configs import MeshConfig, get_preset
    from proteinbert_tpu.models import proteinbert
    from proteinbert_tpu.parallel import make_mesh
    from proteinbert_tpu.serve import RaggedDispatcher

    devices = jax.devices()
    assert len(devices) == 4, devices
    rows, L, S = args["rows"], args["seq_len"], args["segments"]
    cfg = get_preset(args["preset"])
    cfg = cfg.replace(data=dataclasses.replace(cfg.data, seq_len=L),
                      mesh=MeshConfig(data=2, fsdp=2))
    params = proteinbert.init(jax.random.PRNGKey(0), cfg.model)
    rng = np.random.default_rng(42)
    tokens = rng.integers(4, 24, size=(rows, L)).astype(np.int32)
    seg = np.zeros((rows, L), np.int32)
    for r in range(rows):  # S spans a row, a pad tail behind the last
        cuts = [0] + sorted(rng.choice(np.arange(8, L - 8), S - 1,
                                       replace=False)) + [L - 5]
        for i in range(S):
            seg[r, cuts[i]:cuts[i + 1]] = i + 1
    tokens[seg == 0] = 0
    ann = (rng.random((rows, S, cfg.model.num_annotations)) < 0.005
           ).astype(np.float32)

    before = dict(K.PATH_TOTAL)
    d = RaggedDispatcher(params, cfg, rows_per_batch=rows, max_segments=S,
                         mesh=make_mesh(cfg.mesh, devices))
    fn = d._packed_fn("embed")
    placed = d._place_packed(tokens, seg, ann)
    # Compiled once, ahead of time: the same executable gives the text
    # and takes every call.
    mesh_exe = fn.lower(d.params, *placed, cfg.model).compile()
    text = mesh_exe.as_text()
    got = mesh_exe(d.params, *placed)
    q = rows // 4
    one = jax.device_put((params, tokens[:q], seg[:q], ann[:q]), devices[0])
    want = inference._packed_encode_batch(*one, cfg.model)
    out = {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "rows": rows, "seq_len": L,
        "devices_holding_outputs": min(
            len(a.sharding.device_set) for a in jax.tree.leaves(got)),
        "worst_gap": round(max(float(np.max(np.abs(
            np.asarray(a, np.float32)[:q] - np.asarray(b, np.float32))))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))),
            6),
        "collectives": sorted(c for c in (
            "all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all") if c + "(" in text
            or c + "-start(" in text),
        "tpu_custom_calls": text.count("tpu_custom_call"),
        "fused_path": {f"{p}/{r}": c - before.get((p, r), 0)
                       for (p, r), c in sorted(K.PATH_TOTAL.items())
                       if c != before.get((p, r), 0)},
    }
    if out["platform"] == "tpu":
        for name, call in (
                ("mesh_ms", lambda: mesh_exe(d.params, *placed)),
                ("one_chip_quarter_ms",
                 lambda: inference._packed_encode_batch(*one, cfg.model))):
            jax.block_until_ready(call())
            t0 = time.perf_counter()
            for _ in range(10):
                y = call()
            jax.block_until_ready(y)
            out[name] = round((time.perf_counter() - t0) / 10 * 1e3, 3)
    print(json.dumps(out))


# --------------------------------------------------------- phase: multichip

def phase_multichip(opts, size) -> None:
    t0 = time.monotonic()
    out = last_json("multichip", run_child("multichip", "multichip", fn_cmd(
        "multichip", {**size["multichip"],
                      "cpu_devices": 4 if opts.platform == "cpu" else 0,
                      "tol": MULTICHIP_LOSS_TOL}), opts, cap=1100))
    for run in out["runs"]:
        worst = max(abs(a - b) for a, b in zip(run["losses"],
                                               run["reference_losses"]))
        check(worst <= MULTICHIP_LOSS_TOL, "multichip",
              f"{run['name']}: losses {run['losses']} vs one device "
              f"{run['reference_losses']} (|Δ| {worst:.4f} > "
              f"{MULTICHIP_LOSS_TOL})")
        check(run["devices_holding_shards"] == 4
              and run["whole_on_one_device"] == [], "multichip",
              f"{run['name']}: sharded leaves not spread over the four "
              f"devices: {run}")
        missing = [c for c in run["expected_collectives"]
                   if not any(alt in run["collectives"] for alt in c)]
        check(not missing, "multichip",
              f"{run['name']}: compiled text lacks {missing}; has "
              f"{run['collectives']}")
    emit("multichip", t0, compile_stats("multichip"), out)


def _child_multichip(payload: str) -> None:
    """The sharded trainer against one device of the same host: same init,
    same batches, (a) data=2 x fsdp=2 through train_step and through the
    ZeRO-1 step, (b) seq=4 at the long preset's window through the
    implicit-SPMD step. The structure of __graft_entry__.dryrun_multichip
    at real width."""
    args = json.loads(payload)
    import dataclasses

    import jax
    import numpy as np

    from proteinbert_tpu.utils.compat import (
        configure_compile_cache, request_cpu_devices,
    )

    if args["cpu_devices"]:
        request_cpu_devices(args["cpu_devices"])
    configure_compile_cache()
    from proteinbert_tpu.configs import (
        MeshConfig, ParallelConfig, get_preset,
    )
    from proteinbert_tpu.parallel import (
        batch_sharding, make_mesh, make_zero_train_step, pin_state_sharding,
        shard_train_state,
    )
    from proteinbert_tpu.train import create_train_state, train_step

    devices = jax.devices()
    assert len(devices) == 4, devices
    k = args["steps"]

    def sized(preset):
        cfg = get_preset(preset)
        opt = dataclasses.replace(cfg.optimizer, warmup_steps=2)
        return cfg.replace(optimizer=opt, mesh=MeshConfig())

    def batches(cfg, seed):
        rng = np.random.default_rng(seed)
        B, L, A = (cfg.data.batch_size, cfg.data.seq_len,
                   cfg.model.num_annotations)
        out = []
        for _ in range(k):
            tok = rng.integers(4, 24, size=(B, L)).astype(np.int32)
            for b in range(B):  # ragged real lengths, zero = <pad>
                tok[b, rng.integers(L // 4, L):] = 0
            out.append({"tokens": tok,
                        "annotations": (rng.random((B, A)) < 0.005
                                        ).astype(np.float32)})
        return out

    def one_device(cfg, data):
        state = create_train_state(jax.random.PRNGKey(0), cfg)
        losses = []
        for b in data:
            state, m = train_step(state, jax.device_put(b, devices[0]), cfg)
            losses.append(float(m["loss"]))
        return losses

    def bytes_in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in devices]

    def sharded(name, cfg, mesh_cfg, data, zero, expected):
        mesh = make_mesh(mesh_cfg, devices)
        cfg = cfg.replace(mesh=mesh_cfg,
                          parallel=ParallelConfig(zero_update=zero))
        state = shard_train_state(
            create_train_state(jax.random.PRNGKey(0), cfg), mesh,
            zero_update=zero)
        bsh = batch_sharding(mesh)

        def put(b):
            return {kk: jax.device_put(v, bsh[kk]) for kk, v in b.items()}

        first = put(data[0])
        # After placement: every array the rules split (train state under
        # fsdp/ZeRO, the batch under every layout) is held in pieces, by
        # all four devices; none of them sits whole on one device.
        holders, whole, split = set(), [], 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(
                {"state": state, "batch": first}):
            if leaf.sharding.is_fully_replicated:
                continue
            split += 1
            for sh in leaf.addressable_shards:
                holders.add(sh.device.id)
                if sh.data.shape == leaf.shape:
                    whole.append(jax.tree_util.keystr(path))
        placed = bytes_in_use()
        # The trainer's own arrangement (train/trainer.pretrain): the step
        # of the layout, pinned to the state's sharding. Compiled once,
        # ahead of time: the same executable gives the text the
        # collectives are read from and takes every step.
        step = pin_state_sharding(
            make_zero_train_step(mesh, cfg) if zero
            else (lambda s, b: train_step(s, b, cfg)), state,
        ).lower(state, first).compile()
        text = step.as_text()
        kinds = sorted(c for c in (
            "all-reduce", "all-gather", "reduce-scatter",
            "collective-permute", "all-to-all") if c in text)
        losses = []
        for b in data:
            state, m = step(state, put(b))
            losses.append(float(m["loss"]))
        assert int(jax.device_get(state.step)) == k
        return {"name": name, "mesh": {a: n for a, n in zip(
                    mesh_cfg.axis_names, mesh_cfg.shape) if n > 1},
                "batch": [cfg.data.batch_size, cfg.data.seq_len],
                "losses": losses, "split_leaves": split,
                "devices_holding_shards": len(holders),
                "whole_on_one_device": whole[:5],
                "bytes_in_use_after_placement": placed,
                "collectives": kinds, "expected_collectives": expected}

    base = sized(args["preset"])
    data = batches(base, 1)
    ref = one_device(base, data)
    grad_sync = ["all-reduce", "reduce-scatter"]
    runs = []
    for name, zero in (("data2_fsdp2", False), ("data2_fsdp2_zero1", True)):
        r = sharded(name, base, MeshConfig(data=2, fsdp=2), data, zero,
                    [grad_sync, ["all-gather"]])
        r["reference_losses"] = ref
        runs.append(r)
    long_cfg = sized(args["long_preset"])
    long_data = batches(long_cfg, 2)
    long_ref = one_device(long_cfg, long_data)
    r = sharded("seq4", long_cfg, MeshConfig(seq=4), long_data, False,
                [["all-reduce"], ["collective-permute"]])
    r["reference_losses"] = long_ref
    runs.append(r)
    print(json.dumps({
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices), "steps": k, "tolerance": args["tol"],
        "runs": runs,
    }))


CHILD_PHASES = {"device": _child_device, "kernels": _child_kernels,
                "served_mesh": _child_served_mesh,
                "multichip": _child_multichip}


# --------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the served mesh executable and the "
                         "sharded-trainer path, each with the one-device "
                         "run it is compared with")
    ap.add_argument("--platform", choices=("cpu",),
                    help="sandbox rehearsal at tiny width; the last line "
                         "then names the cpu")
    opts = ap.parse_args(argv)
    size = SIZES["cpu" if opts.platform == "cpu" else "real"]
    shutil.rmtree(LOG_DIR, ignore_errors=True)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as work:
        device = phase_device(opts)
        if opts.chips == 4:
            phase_served_mesh(opts, size)
            phase_multichip(opts, size)
        else:
            run_dir = phase_pretrain(opts, size, work, device)
            probes = {mode: phase_serve(opts, size, work, run_dir, mode)
                      for mode in ("bucketed", "ragged")}
            a, b = (probes[m]["probe"]["global"]
                    for m in ("bucketed", "ragged"))
            worst = max(abs(x - y) for x, y in zip(a, b))
            check(worst <= SERVE_MODE_TOL, "serve",
                  "the probe sequence's embedding differs between the "
                  f"bucketed and ragged servers by {worst:.4f}")
            phase_map(opts, size, work, run_dir)
            phase_kernels(opts, size)
    print(json.dumps({
        "total_seconds": round(time.monotonic() - _T0, 1),
        "logs": os.path.relpath(LOG_DIR, REPO)}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
