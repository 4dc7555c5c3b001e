"""Test harness: 8 virtual CPU devices, set up BEFORE jax initializes.

Every sharding/collective test in this suite runs against a fake
8-device CPU mesh (SURVEY.md §4); the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip, and
`chip_smoke.py --chips 4` is the same check at width on real chips.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# The suite is compile-bound on CPU (the same train-step HLO is rebuilt
# by many tests), so it keeps the persistent compilation cache — in the
# one place every entry point uses (utils/compat.configure_compile_cache)
# — and skips only the sub-0.3 s compiles, whose cache traffic costs
# more than it saves. The variable is inherited by every subprocess a
# test starts.
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")

import jax  # noqa: E402  (import after env setup is the point)

from proteinbert_tpu.utils.compat import (  # noqa: E402
    configure_compile_cache, request_cpu_devices,
)

request_cpu_devices(8)
configure_compile_cache()

# Fail at collection, loudly and once, if the 8-device CPU mesh did not
# come up — otherwise every sharding/collective test fails later with a
# confusing "axis size mismatch" instead of the real cause.
if jax.device_count() < 8:
    raise RuntimeError(
        f"test harness needs 8 virtual CPU devices, got "
        f"{jax.device_count()} — the backend was initialized before "
        "jax_num_cpu_devices=8 could apply")

import numpy as np
import pytest

# ---------------------------------------------------------------- map relief
# XLA's CPU thunk runtime JIT-maps every compiled kernel as its own small
# executable mapping and never unmaps it; a full-suite run accumulates
# ~60k mappings and segfaults inside LLVM when the process hits the
# kernel's vm.max_map_count (65530 default) — observed twice, always at
# the same test. Tearing the backend down releases them (measured
# 3320 → 610). This valve fires between MODULES only: module-scoped
# fixtures (tests/test_inference.py's `trunk`) legally hold device arrays
# across tests within a module, and a mid-module reset would kill them.
#
# INVARIANT for test authors: NO live jax.Array may be held across a
# module boundary — not via module-scoped fixtures only, but ANY
# mechanism (module-level globals, session-scoped fixtures, caches like
# functools.lru_cache over device arrays). clear_backends() invalidates
# every buffer created before it runs; a cross-module array surfaces
# later as a confusing "deleted/donated buffer" error in an unrelated
# test. Keep device state module-local, or re-create it per module.

_MAP_RESET_THRESHOLD = 35_000


def _map_count() -> int:
    try:
        with open("/proc/self/maps") as f:
            return sum(1 for _ in f)
    except OSError:  # non-Linux: no /proc, no known map ceiling either
        return 0


@pytest.fixture(autouse=True, scope="module")
def _jax_map_pressure_relief():
    if _map_count() >= _MAP_RESET_THRESHOLD:
        import gc

        import jax.extend.backend

        jax.clear_caches()
        jax.extend.backend.clear_backends()
        gc.collect()
    yield


# ---------------------------------------------------------- marker audit
# --strict-markers (pyproject) rejects UNREGISTERED marks; this check
# covers the other failure mode — a registered-but-FORGOTTEN mark. The
# scale tiers spawn multi-minute children; if a test in one of these
# modules ships without `slow`, tier-1's `-m 'not slow'` run collects it
# and the 870 s budget dies quietly. Fail at collection, naming the test.

_SLOW_REQUIRED_MODULES = ("test_parallel64", "test_multihost")


# ------------------------------------------- pinned to an older manifest
# Three assertions of `tests/benchmark/test_ling_cell.py` pin
# `BENCHMARK.json` by place and by count as PR 33 left it (six cells, its
# own entries last, its lists of two). ISSUE 35 appends a seventh cell,
# and a PR that is not of kind `benchmark` may not edit a file under the
# benchmark's `paths` (`tests/benchmark/` is one), so they are marked
# from HERE, outside those paths: expected to fail, strictly, until a
# `benchmark` PR rewrites them. What each held of the manifest,
# `tests/benchmark/test_zaya_cell.py` asserts by name. (With the three
# that `tests/benchmark/conftest.py` marks and PR 39's two below, a
# `benchmark` PR has eight such lines to put right: PERF.md section 7.)
PINNED_TO_AN_OLDER_MANIFEST = {
    "benchmark/test_ling_cell.py::"
    "test_the_manifest_lists_the_cell_its_configuration_and_its_metrics":
        "reads its cell, configuration and metrics as the LAST entries and "
        "its lists as two cells; PR 35 appended its own after them",
    "benchmark/test_ling_cell.py::"
    "test_the_manifest_has_six_cells_and_one_on_four_chips":
        "asserts six cells; PR 35 added the seventh",
    "benchmark/test_ling_cell.py::"
    "test_the_older_entries_stand_as_they_were_but_for_the_cells_name":
        "asserts two cells in mfu_pct.tput's list; PR 35 appended its cell",
}
# PR 39 appended start-up metrics that list EVERY cell, and two
# assertions of `tests/benchmark/test_zaya_cell.py` hold the set of
# metrics of its cell whole (a dictionary of their own: a third
# assertion there counts the three above). Every line the two held,
# `tests/benchmark/test_startup_metrics.py` (the manifest's: the cell,
# its configuration and source, the bound, its own six metrics, the
# twenty it shares, the set) and `tests/benchmark/test_startup_rehearsal.py`
# (the traced rehearsal's line) assert by name.
PINNED_SINCE_PR_39 = {
    "benchmark/test_zaya_cell.py::"
    "test_the_manifest_lists_the_cell_its_configuration_and_its_metrics":
        "asserts the WHOLE set of metrics that list its cell; PR 39 "
        "appended six that list every serving cell",
    "benchmark/test_zaya_cell.py::test_rehearsal_prints_the_contracts_line[1]":
        "asserts that a traced rehearsal prints no metric but the cell's "
        "twenty-six; PR 39's six are on the line too",
}
# ISSUE 43 appends an eighth cell, a fourth serving one. Nine assertions
# under `tests/benchmark/` hold a list of `BENCHMARK.json` WHOLE (the
# cells of the start-up metrics and of `scope_map_s.tput`; the one cell of
# `moe_experts_roofline.tput`, which the new cell joins: one share of one
# scope is not doubled under a second name) or the manifest's ORDER
# (`scope_map_s.tput` listed after every reader that asks for a scope map:
# a PR may only append, so the five new readers stand after it; in every
# cell that lists one of them an earlier reader has asked by then, and the
# map is made once a class). Marked from here as the two dictionaries above
# are, in a dictionary of their own (`test_zaya_cell.py` reads those two
# and requires them to match no test). What each held,
# `tests/benchmark/test_nemotron_cell.py` asserts by name and as
# "contains", a case for each; a `benchmark` PR has these nine lines to
# put right (PERF.md section 7).
_LISTS_WHOLE = ("holds the metric's list of cells whole "
                "(`sorted(workloads) == sorted(SERVE)`); PR 43 appended its cell")
PINNED_SINCE_PR_43 = {
    **{"benchmark/test_startup_metrics.py::"
       f"test_the_manifest_lists_the_metric_by_name_with_a_reader[{name}]":
           _LISTS_WHOLE
       for name in ("startup_compile_s", "startup_compiles", "startup_cache_load_s",
                    "startup_trace_lower_s", "startup_warmup_s", "scope_map_s.tput")},
    "benchmark/test_startup_metrics.py::"
    "test_the_second_served_decoders_own_metric_stands_as_written"
    "[moe_experts_roofline.tput]":
        "holds `moe_experts_roofline.tput`'s list as ZAYA1's cell alone; PR "
        "43's cell reports the same share of the same scope and joined it",
    "benchmark/test_zaya_cell.py::"
    "test_the_manifest_lists_zayas_cell_configuration_and_metrics_by_name":
        "holds `moe_experts_roofline.tput`'s list as its cell alone; PR 43's "
        "cell joined it",
    "benchmark/test_startup_metrics.py::"
    "test_the_scope_maps_seconds_are_read_after_every_reader_that_asks":
        "holds `scope_map_s.tput` after EVERY reader that asks for a scope "
        "map; a PR may only append, so PR 43's five readers stand after it",
}


def pytest_collection_modifyitems(config, items):
    for item in items:
        for tail, why in {**PINNED_TO_AN_OLDER_MANIFEST, **PINNED_SINCE_PR_39,
                          **PINNED_SINCE_PR_43}.items():
            if item.nodeid.endswith(tail):
                item.add_marker(pytest.mark.xfail(reason=why, strict=True))
    unmarked = [
        item.nodeid for item in items
        if item.module.__name__.rsplit(".", 1)[-1] in _SLOW_REQUIRED_MODULES
        and "slow" not in item.keywords
    ]
    if unmarked:
        raise pytest.UsageError(
            "scale-tier tests must carry the `slow` marker (tier-1's "
            "timeout budget assumes -m 'not slow' excludes them): "
            + ", ".join(unmarked))
    for item in items:
        if "tier64" in item.keywords and "slow" not in item.keywords:
            raise pytest.UsageError(
                f"{item.nodeid}: tier64 tests must also be marked slow")


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def key():
    return jax.random.PRNGKey(0)


from proteinbert_tpu.data.synthetic import make_random_proteins  # noqa: E402


@pytest.fixture
def random_proteins(rng):
    return make_random_proteins(64, rng)
