"""`pbt check` static analyzer (ISSUE 15): per-rule seeded-violation +
clean fixtures, baseline round-trip, --json schema, and the repo-wide
zero-findings smoke that IS the tier-1 gate's contract.

Fixtures are tiny trees written under tmp_path and run through the
same `run_check` the tier-1 stage uses — no monkeypatching of rule
internals, so a rule that silently stopped matching its pattern fails
its seeded fixture here before it silently passes the repo."""

import functools
import json
import os
import re
import subprocess
import sys
import textwrap

import pytest

from proteinbert_tpu.analysis import (
    CheckConfig, load_baseline, run_check, save_baseline,
    split_by_baseline,
)
from proteinbert_tpu.analysis.findings import BaselineError, report_dict

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def write_tree(root, files):
    for rel, content in files.items():
        path = root / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(content))


def fixture_cfg(root, **overrides):
    defaults = dict(
        root=str(root), scan_roots=("pkg",), durability_files=(),
        events_py="pkg/events.py", docs_md="docs.md",
        reference_roots=("pkg",))
    defaults.update(overrides)
    return CheckConfig(**defaults)


def keys(result):
    return {f.key for f in result["findings"]}


def rules_hit(result):
    return {f.rule for f in result["findings"]}


# ------------------------------------------------------------ rule 1

JIT_VIOLATION = """
    import os
    import random
    import time

    import jax
    import numpy as np


    def helper(x):
        return x * time.time()          # clock at trace time


    def step(x):
        if os.environ.get("MY_FLAG"):   # unsanctioned env read
            x = x + random.random()     # host randomness
        return helper(x) + np.random.rand()


    train = jax.jit(step)
"""

JIT_CLEAN = """
    import time

    import jax


    def sanctioned_reader():
        import os
        return bool(os.environ.get("FLAG"))


    def step(x):
        return x * 2 if sanctioned_reader() else x


    def host_loop(x):
        t0 = time.time()                # host side: fine
        return jax.jit(step)(x), time.time() - t0
"""


def test_jit_purity_seeded_violation(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": JIT_VIOLATION})
    res = run_check(fixture_cfg(tmp_path), rules=["jit-purity"])
    got = keys(res)
    # All four host-state classes flagged, including through the
    # module-local call chain (step -> helper).
    assert any("time.time" in k and "helper" in k for k in got)
    assert any("os.environ" in k for k in got)
    assert any("random.random" in k for k in got)
    assert any("np.random.rand" in k for k in got)


def test_jit_purity_clean_and_sanctioned(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": JIT_CLEAN})
    cfg = fixture_cfg(tmp_path,
                      sanctioned_env_readers=("sanctioned_reader",))
    res = run_check(cfg, rules=["jit-purity"])
    assert res["findings"] == []


def test_jit_purity_pallas_dispatch_wrapper_is_a_root(tmp_path):
    # A function CONTAINING a pallas_call runs at trace time of the
    # (cross-module) jit that wraps it — its body is held to the bar.
    write_tree(tmp_path, {"pkg/k.py": """
        import os
        from jax.experimental import pallas as pl


        def kernel(ref):
            ref[...] = ref[...] * 2


        def dispatch(x):
            if os.environ.get("FORCE_SLOW"):
                return x
            return pl.pallas_call(kernel)(x)
    """})
    res = run_check(fixture_cfg(tmp_path), rules=["jit-purity"])
    assert any("os.environ" in k and "dispatch" in k for k in keys(res))


def test_jit_purity_composed_dispatch_chain(tmp_path):
    # The one-pass trunk pattern (ISSUE 16): a dispatch wrapper whose
    # FALLBACK path calls another dispatch wrapper. Host state anywhere
    # along the composed chain (onepass -> inner) is still trace-time
    # state of the outer jit, so the rule must flag it through the
    # chain — while the sanctioned force-override reader stays clean.
    write_tree(tmp_path, {"pkg/k.py": """
        import time

        from jax.experimental import pallas as pl


        def force_reference_requested():
            import os
            return bool(os.environ.get("FORCE_SLOW"))


        def kernel(ref):
            ref[...] = ref[...] * 2


        def inner(x):
            x = x * time.time()         # clock at trace time
            return pl.pallas_call(kernel)(x)


        def onepass(x):
            if force_reference_requested():   # sanctioned: clean
                return inner(x)
            return pl.pallas_call(kernel)(x)
    """})
    cfg = fixture_cfg(
        tmp_path, sanctioned_env_readers=("force_reference_requested",))
    res = run_check(cfg, rules=["jit-purity"])
    got = keys(res)
    assert any("time.time" in k and "inner" in k for k in got)
    assert not any("os.environ" in k for k in got)


# ------------------------------------------------------------ rule 2

LOCK_VIOLATION = """
    import threading


    class Router:
        def __init__(self):
            self._lock = threading.Lock()
            self.sealed = 0        # guarded-by: _lock
            self.ended = False     # guarded-by: _lock

        def seal(self):
            with self._lock:
                self.sealed += 1

        def drain(self):
            if not self.ended:     # unlocked read
                self.ended = True  # unlocked write
"""

LOCK_CLEAN = """
    import threading


    class Router:
        def __init__(self):
            self._lock = threading.Lock()
            self.sealed = 0        # guarded-by: _lock

        def seal(self):
            with self._lock:
                self.sealed += 1

        def _report(self):  # lock-held: _lock
            return self.sealed

        def stats(self):
            with self._lock:
                return {"sealed": self.sealed, "r": self._report()}
"""


def test_lock_discipline_seeded_violation(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": LOCK_VIOLATION})
    res = run_check(fixture_cfg(tmp_path), rules=["lock-discipline"])
    got = keys(res)
    assert "lock-discipline::pkg/m.py::Router.drain:ended" in got
    # seal() is locked — must NOT be flagged.
    assert not any("seal" in k for k in got)


def test_lock_discipline_clean_with_lock_held_annotation(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": LOCK_CLEAN})
    res = run_check(fixture_cfg(tmp_path), rules=["lock-discipline"])
    assert res["findings"] == []


def test_lock_discipline_closure_does_not_inherit_region(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": """
        import threading


        class C:
            def __init__(self):
                self._lock = threading.Lock()
                self.n = 0         # guarded-by: _lock

            def make_cb(self):
                def cb():
                    return self.n  # runs later, lock NOT held
                with self._lock:
                    return cb
    """})
    res = run_check(fixture_cfg(tmp_path), rules=["lock-discipline"])
    assert any("make_cb.cb:n" in k for k in keys(res))


def test_lock_order_cycle_detected(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": """
        import threading

        a_lock = threading.Lock()
        b_lock = threading.Lock()


        def one():
            with a_lock:
                with b_lock:
                    pass


        def two():
            with b_lock:
                with a_lock:
                    pass
    """})
    res = run_check(fixture_cfg(tmp_path), rules=["lock-discipline"])
    assert any(k.startswith("lock-discipline::pkg/m.py::lock-order:")
               for k in keys(res))


# ------------------------------------------------------------ rule 3

DURABILITY_VIOLATION = """
    import os


    def save_no_fsync(path, data):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
        os.replace(tmp, path)       # no fsync between write and rename


    def save_bare(path, data):
        with open(path, "wb") as f:  # bytes straight to the final path
            f.write(data)
"""

DURABILITY_CLEAN = """
    import os


    def save(path, data):
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            f.write(data)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)


    def append_log(path, line):
        with open(path, "a", buffering=1) as f:   # append-only: exempt
            f.write(line)
"""


def test_durability_seeded_violations(tmp_path):
    write_tree(tmp_path, {"pkg/store.py": DURABILITY_VIOLATION})
    cfg = fixture_cfg(tmp_path, durability_files=("pkg/store.py",))
    res = run_check(cfg, rules=["durability-protocol"])
    got = keys(res)
    assert any("rename-without-fsync" in k and "save_no_fsync" in k
               for k in got)
    assert any("bare-final-write" in k and "save_bare" in k for k in got)


def test_durability_clean(tmp_path):
    write_tree(tmp_path, {"pkg/store.py": DURABILITY_CLEAN})
    cfg = fixture_cfg(tmp_path, durability_files=("pkg/store.py",))
    res = run_check(cfg, rules=["durability-protocol"])
    assert res["findings"] == []


# ------------------------------------------------------------ rule 4

EVENTS_PY = """
    EVENT_FIELDS = {
        "step": {"step": int, "metrics": dict},
        "note": {"source": str},
        "burn": {"rate": (int, float)},
    }
"""


def test_event_schema_seeded_violations(tmp_path):
    write_tree(tmp_path, {
        "pkg/events.py": EVENTS_PY,
        "pkg/m.py": """
            def go(tele, fields):
                tele.emit("stepp", step=1, metrics={})     # unknown
                tele.emit("step", step=1)                  # missing
                tele.emit("step", step="one", metrics={})  # wrong type
                tele.emit("burn", rate=2)                  # int ok
                tele.emit("step", **fields)                # spread: skip
        """,
    })
    res = run_check(fixture_cfg(tmp_path), rules=["event-schema"])
    got = keys(res)
    assert "event-schema::pkg/m.py::emit:stepp:unknown-event" in got
    assert "event-schema::pkg/m.py::emit:step:missing:metrics" in got
    assert "event-schema::pkg/m.py::emit:step:type:step" in got
    assert len(got) == 3  # burn + spread pass


def test_event_schema_clean(tmp_path):
    write_tree(tmp_path, {
        "pkg/events.py": EVENTS_PY,
        "pkg/m.py": """
            def go(tele, n):
                tele.emit("step", step=n, metrics={"loss": 1.0})
                tele.emit("note", source="test", extra=True)
        """,
    })
    res = run_check(fixture_cfg(tmp_path), rules=["event-schema"])
    assert res["findings"] == []


# ------------------------------------------------------------ rule 5

def test_obs_doc_drift_seeded_violations(tmp_path):
    write_tree(tmp_path, {
        "pkg/events.py": EVENTS_PY,
        "pkg/m.py": """
            def instruments(reg):
                reg.counter("undocumented_thing_total").inc()
                reg.gauge("documented_depth").set(1)
        """,
        "docs.md": """
            # doc

            ## Event schema

            | event | payload |
            |---|---|
            | `step` | `step`, `metrics` |
            | `note` | `source` |
            | `ghost_event` | gone |

            ## Metric names

            `documented_depth` and `ghost_metric_total` are exported.

            ## Next section
        """,
    })
    res = run_check(fixture_cfg(tmp_path), rules=["obs-doc-drift"])
    got = keys(res)
    assert any("event-undocumented:burn" in k for k in got)
    assert any("event-ghost:ghost_event" in k for k in got)
    assert any("metric-undocumented:undocumented_thing_total" in k
               for k in got)
    assert any("metric-ghost:ghost_metric_total" in k for k in got)


def test_obs_doc_drift_clean_with_brace_expansion(tmp_path):
    write_tree(tmp_path, {
        "pkg/events.py": """
            EVENT_FIELDS = {"note": {"source": str}}
        """,
        "pkg/m.py": """
            def instruments(reg):
                reg.counter("cache_hits_total").inc()
                reg.counter("cache_misses_total").inc()
        """,
        "docs.md": """
            ## Event schema

            | `note` | `source` |

            ## Metric names

            `cache_{hits,misses}_total` counters.
        """,
    })
    res = run_check(fixture_cfg(tmp_path), rules=["obs-doc-drift"])
    assert res["findings"] == []


# ----------------------------------------- paths the documents name

USER_DOCS = ["README.md"] + sorted(
    "docs/" + f for f in os.listdir(os.path.join(REPO, "docs"))
    if f.endswith(".md"))
PATH_ROOTS = ("proteinbert_tpu/", "tools/", "tests/", "benchmark/",
              "examples/", "docs/")


@functools.lru_cache(maxsize=None)
def _known_paths():
    """Every file git would commit, and every directory above one; a
    checkout without its .git is walked."""
    proc = subprocess.run(["git", "ls-files"], capture_output=True,
                          text=True, cwd=REPO)
    files = proc.stdout.split() if proc.returncode == 0 else []
    if not files:
        files = [os.path.relpath(os.path.join(d, f), REPO)
                 for d, _, names in os.walk(REPO) for f in names]
    known = set(files)
    for f in files:
        while "/" in f:
            f = f.rsplit("/", 1)[0]
            known.add(f)
    return known


@pytest.mark.parametrize("doc", USER_DOCS)
def test_paths_a_document_names_exist(doc):
    """A document a user reads names no file that is gone: every
    backticked token that begins with a directory of this repo (its
    arguments, a trailing `:line` or `::name` stripped; globs and
    placeholders left out) is a file or directory git knows."""
    known = _known_paths()
    with open(os.path.join(REPO, doc)) as f:
        tokens = re.findall(r"`([^`\n]+)`", f.read())
    dangling = []
    for tok in tokens:
        if not tok.startswith(PATH_ROOTS) or any(c in tok for c in "*<{"):
            continue
        path = re.sub(r"(::.*|:\d+(-\d+)?)$", "", tok.split()[0])
        if path.rstrip("/") not in known:
            dangling.append(tok)
    assert not dangling, f"{doc} names paths that do not exist: {dangling}"


# ------------------------------------------------------------ rule 6

def test_dead_export_seeded_violation(tmp_path):
    write_tree(tmp_path, {
        "pkg/__init__.py": """
            from pkg.mod import used_fn, dead_fn

            __all__ = ["used_fn", "dead_fn"]
        """,
        "pkg/mod.py": """
            def used_fn():
                return 1


            def dead_fn():
                return 2
        """,
        "pkg/caller.py": """
            from pkg.mod import used_fn

            print(used_fn())
        """,
    })
    res = run_check(fixture_cfg(tmp_path), rules=["dead-export"])
    got = keys(res)
    assert "dead-export::pkg/__init__.py::export:dead_fn" in got
    assert not any("used_fn" in k for k in got)


def test_dead_export_clean(tmp_path):
    write_tree(tmp_path, {
        "pkg/__init__.py": """
            from pkg.mod import used_fn

            __all__ = ["used_fn"]
        """,
        "pkg/mod.py": "def used_fn():\n    return 1\n",
        "pkg/caller.py": "from pkg.mod import used_fn\nused_fn()\n",
    })
    res = run_check(fixture_cfg(tmp_path), rules=["dead-export"])
    assert res["findings"] == []


# ---------------------------------------------------- parse gate

def test_syntax_error_is_a_finding_not_a_skip(tmp_path):
    write_tree(tmp_path, {"pkg/broken.py": "def broken(:\n"})
    res = run_check(fixture_cfg(tmp_path), rules=["jit-purity"])
    assert any(f.rule == "parse" for f in res["findings"])


def test_write_baseline_refuses_to_suppress_syntax_errors(tmp_path):
    """A baselined parse finding would let every rule silently skip
    that file forever — --write-baseline must refuse (exit 2), never
    stub it."""
    write_tree(tmp_path, {
        "proteinbert_tpu/broken.py": "def broken(:\n",
        "proteinbert_tpu/obs/events.py": "EVENT_FIELDS = {}\n",
        "docs/observability.md": "## Event schema\n\n## Metric names\n",
    })
    baseline = str(tmp_path / "b.json")
    proc = run_pbt_check("--root", str(tmp_path), "--baseline",
                         baseline, "--write-baseline")
    assert proc.returncode == 2, proc.stdout + proc.stderr
    assert not os.path.exists(baseline)


# ---------------------------------------------------- baseline

def test_baseline_round_trip_and_staleness(tmp_path):
    write_tree(tmp_path, {"pkg/m.py": LOCK_VIOLATION})
    cfg = fixture_cfg(tmp_path)
    res = run_check(cfg, rules=["lock-discipline"])
    assert res["findings"]
    path = str(tmp_path / "baseline.json")
    save_baseline(path, {f.key: "accepted: fixture debt"
                         for f in res["findings"]})
    loaded = load_baseline(path)
    new, suppressed, stale = split_by_baseline(res["findings"], loaded)
    assert new == [] and len(suppressed) == len(res["findings"])
    assert stale == []
    # An entry whose violation is gone must surface as stale.
    loaded["lock-discipline::pkg/gone.py::X.y:z"] = "paid down"
    new, suppressed, stale = split_by_baseline(res["findings"], loaded)
    assert stale == ["lock-discipline::pkg/gone.py::X.y:z"]


def test_baseline_requires_reasons(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps(
        {"v": 1, "suppressions": [{"key": "a::b::c", "reason": "  "}]}))
    with pytest.raises(BaselineError):
        load_baseline(str(path))


def test_report_dict_json_schema():
    rep = report_dict([], [], [], {}, ["jit-purity"])
    assert rep["v"] == 1 and rep["kind"] == "pbt_check_report"
    assert rep["ok"] is True
    assert rep["counts"] == {"new": 0, "baselined": 0,
                             "stale_baseline": 0,
                             "check_findings_total": 0}
    json.dumps(rep)  # strict-JSON-able


# ---------------------------------------------------- CLI / gate

def run_pbt_check(*argv):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pbt_check.py"),
         *argv],
        capture_output=True, text=True, cwd=REPO)


def test_tier1_gate_fails_on_injected_violation(tmp_path):
    """The acceptance-criteria smoke: the tier-1 stage (the same
    tools/pbt_check.py invocation run_tier1.sh makes) must exit
    nonzero on a tree with an injected violation."""
    write_tree(tmp_path, {
        "proteinbert_tpu/bad.py": LOCK_VIOLATION,
        # Minimal schema/doc so the other rules run without config
        # errors against this synthetic root.
        "proteinbert_tpu/obs/events.py": 'EVENT_FIELDS = {}\n',
        "docs/observability.md": "## Event schema\n\n## Metric names\n",
    })
    proc = run_pbt_check("--root", str(tmp_path), "--json")
    assert proc.returncode == 1, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["ok"] is False
    assert any(f["rule"] == "lock-discipline" for f in rep["findings"])
    # --write-baseline then re-check: the gate goes green, proving the
    # suppression path end to end.
    baseline = str(tmp_path / "b.json")
    assert run_pbt_check("--root", str(tmp_path), "--baseline", baseline,
                         "--write-baseline").returncode == 0
    proc = run_pbt_check("--root", str(tmp_path), "--baseline", baseline,
                         "--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["counts"]["new"] == 0 and rep["counts"]["baselined"] > 0


def test_repo_smoke_zero_nonbaselined_findings():
    """THE repo gate: `pbt check` over the real tree with the real
    baseline is clean, jax-free, and the baseline holds <= 5 entries
    (ISSUE 15 acceptance criteria)."""
    proc = run_pbt_check("--json")
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rep = json.loads(proc.stdout)
    assert rep["ok"] is True and rep["findings"] == []
    assert rep["counts"]["baselined"] <= 5
    assert rep["stale_baseline"] == []
    assert set(rep["rules"]) == {
        "jit-purity", "lock-discipline", "durability-protocol",
        "event-schema", "obs-doc-drift", "dead-export"}


def test_pbt_check_runs_without_jax(tmp_path):
    """tools/pbt_check.py must work where jax cannot import — the
    whole point of the stub-package entry. Simulate by poisoning jax
    on the import path."""
    (tmp_path / "jax").mkdir()
    (tmp_path / "jax" / "__init__.py").write_text(
        'raise ImportError("jax must not be imported by pbt check")\n')
    env = dict(os.environ,
               PYTHONPATH=str(tmp_path) + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "pbt_check.py")],
        capture_output=True, text=True, cwd=REPO, env=env)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_schema_sync_mode_covers_every_event_type():
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "tools", "validate_events.py"),
         "--schema-sync"],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "schema-sync OK" in proc.stdout


# ------------------------------------------- fixed-violation regressions

def test_scheduler_stats_counts_is_locked_and_coherent():
    """Regression for the unlocked stats()-path reads the lock rule
    surfaced (ISSUE 15 satellite): the dispatch counters update under
    _pending_lock and read through one locked stats_counts()."""
    import threading

    from proteinbert_tpu.serve.queue import RequestQueue
    from proteinbert_tpu.serve.scheduler import MicroBatchScheduler

    class StubDispatcher:
        class cfg:
            class model:
                num_annotations = 0

        def batch_class(self, n):
            return n

        def run(self, kind, tokens, annotations):
            return [t for t in tokens]

    sched = MicroBatchScheduler(RequestQueue(8), StubDispatcher(),
                                finalize=lambda req, row: None)
    assert sched.stats_counts() == (0, 0, 0)
    # The locked read must not deadlock against a concurrent locked
    # update (both sides use _pending_lock).
    done = []

    def reader():
        for _ in range(200):
            sched.stats_counts()
        done.append(True)

    t = threading.Thread(target=reader)
    t.start()
    for _ in range(200):
        with sched._pending_lock:
            sched.batches_total += 1
    t.join(timeout=10)
    assert done and sched.stats_counts()[0] == 200


def test_fleet_drain_emits_exactly_one_terminal_record():
    """Regression for the unlocked `_ended` latch in FleetRouter.drain
    (ISSUE 15 satellite): concurrent drains seal exactly once."""
    import threading

    from proteinbert_tpu import obs
    from proteinbert_tpu.serve.fleet import FleetRouter

    class RecordingTele(obs.Telemetry):
        def __init__(self):
            super().__init__(metrics=False)
            self.fleet_ends = 0
            self._count_lock = threading.Lock()

        def emit(self, event, **fields):
            if event == "fleet_end":
                with self._count_lock:
                    self.fleet_ends += 1
            return None

    tele = RecordingTele()
    router = FleetRouter(["http://127.0.0.1:1"], telemetry=tele,
                         health_interval_s=0)
    threads = [threading.Thread(target=router.drain) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert tele.fleet_ends == 1
