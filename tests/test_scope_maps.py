"""Where a scope map comes from (proteinbert_tpu/obs/tracing.program_scopes):
the executable this process compiled, the map kept beside the compile
cache, or a compile around the cache, against one temporary cache
directory and one device."""

import json
import os
import textwrap

import jax
import jax.numpy as jnp
import pytest

from proteinbert_tpu.obs import tracing
from proteinbert_tpu.obs.metrics import MetricsRegistry, process_counter

SOURCES = ("own_compile", "stored", "compiled")


@pytest.fixture
def cache_dir(tmp_path):
    """An empty persistent cache that keeps every executable, and a
    tracing module that has mapped and counted nothing yet."""
    from jax.experimental.compilation_cache import compilation_cache

    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    directory = str(tmp_path / "cache")
    os.makedirs(directory)
    jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    compilation_cache.reset_cache()
    tracing.arm()
    _forget()
    yield directory
    jax.config.update("jax_compilation_cache_dir", was[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
    compilation_cache.reset_cache()
    _forget()


def _forget():
    """What a fresh process would not know."""
    for table in (tracing._scope_maps, tracing._programs, tracing._uncommitted,
                  tracing._compiled):
        table.clear()


def _counted():
    return {s: process_counter("program_scopes_total", source=s).value
            for s in SOURCES}


def _made(first, second, moved=False, lines_lower=0):
    """jit(f) of one computation: a product and a tanh under `first`, a
    sine and a product under `second`; `moved` takes the sine into
    `first`; `lines_lower` defines the same function that many lines
    further down its file."""
    sine = ("y = jnp.sin(y)", "") if moved else ("", "y = jnp.sin(y)")
    source = "\n" * lines_lower + textwrap.dedent(f"""
        def f(x, w):
            with jax.named_scope({first!r}):
                y = jnp.tanh(x @ w)
                {sine[0] or 'pass'}
            with jax.named_scope({second!r}):
                {sine[1] or 'pass'}
                z = y @ w
            return z.sum()
        """)
    space = {"jax": jax, "jnp": jnp}
    exec(compile(source, "made_for_test_scope_maps.py", "exec"), space)
    return jax.jit(space["f"])


def _arguments():
    device = jax.devices()[0]
    return (jax.device_put(jnp.ones((8, 16)), device),
            jax.device_put(jnp.ones((16, 16)), device))


def _scopes_of(name, jitted):
    args = _arguments()
    jitted(*args).block_until_ready()
    tracing.note_program(name, jitted, args)
    return tracing.program_scopes(name)


def _names(scopes):
    return {part for path in scopes.values() for part in path.split("/")}


def test_a_program_compiled_here_is_read_not_compiled_again(cache_dir):
    before = _counted()
    scopes = _scopes_of("first", _made("alpha", "beta"))
    assert tracing._compiled["jit(f)"] == [1, 0]    # compiled once, never again
    assert {"alpha", "beta"} <= _names(scopes)
    after = _counted()
    assert after["own_compile"] == before["own_compile"] + 1
    assert after["compiled"] == before["compiled"]
    stored = os.listdir(os.path.join(cache_dir, "scope_maps"))
    assert len(stored) == 1 and stored[0].endswith(".json")
    with open(os.path.join(cache_dir, "scope_maps", stored[0])) as f:
        kept = json.load(f)
    assert kept == {"program": "first", "scopes": scopes}
    assert tracing.program_scopes("first") is scopes    # asked again: the memo
    assert _counted() == after


def test_a_loaded_executable_reads_its_own_names_once_then_the_stored_map(
        cache_dir):
    _scopes_of("first", _made("alpha", "beta"))
    before = _counted()
    # The same computation under other names: jax loads the first's entry.
    renamed = _made("gamma", "delta")
    scopes = _scopes_of("renamed", renamed)
    # the first's compile, the load, and the one compile around the cache
    assert tracing._compiled["jit(f)"] == [2, 1]
    assert {"gamma", "delta"} <= _names(scopes)
    assert not {"alpha", "beta"} & _names(scopes)
    assert _counted()["compiled"] == before["compiled"] + 1
    assert len(os.listdir(os.path.join(cache_dir, "scope_maps"))) == 2

    _forget()       # a later process on the same cache directory
    before = _counted()
    again = _scopes_of("renamed", _made("gamma", "delta"))
    assert again == scopes
    after = _counted()
    assert after["stored"] == before["stored"] + 1
    assert after["compiled"] == before["compiled"]
    assert after["own_compile"] == before["own_compile"]
    assert tracing._compiled["jit(f)"][0] == 0      # nothing compiled for it


def test_a_load_while_asking_is_not_trusted_though_the_name_was_compiled_here(
        cache_dir):
    """`jit(f)` was compiled here and never loaded, at ANOTHER shape; the
    lowering asked about was never called, so `lowered.compile()` itself
    loads the cache's entry, which another checkout wrote under its
    names. Those are not this checkout's, and must not be kept as such."""
    _scopes_of("first", _made("alpha", "beta"))     # "another checkout"
    _forget()
    renamed = _made("gamma", "delta")
    device = jax.devices()[0]
    renamed(jax.device_put(jnp.ones((4, 16)), device),
            _arguments()[1]).block_until_ready()
    assert tracing._compiled["jit(f)"] == [1, 0]
    before = _counted()
    tracing.note_program("renamed", renamed, _arguments())
    scopes = tracing.program_scopes("renamed")
    # the other shape's compile, the load while asking, the compile around
    assert tracing._compiled["jit(f)"] == [2, 1]
    assert {"gamma", "delta"} <= _names(scopes)
    assert not {"alpha", "beta"} & _names(scopes)
    after = _counted()
    assert after["compiled"] == before["compiled"] + 1
    assert after["own_compile"] == before["own_compile"]
    _forget()       # and what was kept under this checkout's key is its own
    tracing.note_program("renamed", _made("gamma", "delta"), _arguments())
    assert tracing.program_scopes("renamed") == scopes
    assert _counted()["stored"] == after["stored"] + 1


def test_an_operation_moved_across_a_boundary_gets_a_key_and_a_map_of_its_own(
        cache_dir):
    plain = _scopes_of("plain", _made("alpha", "beta"))
    moved = _scopes_of("moved", _made("alpha", "beta", moved=True))
    assert _names(plain) == _names(moved)           # the same names
    assert set(plain) == set(moved)                 # the same instructions
    assert plain != moved                           # another boundary
    assert len(os.listdir(os.path.join(cache_dir, "scope_maps"))) == 2
    sine = [i for i in plain if plain[i] != moved[i]]
    assert sine and all(plain[i] == "beta" and moved[i] == "alpha" for i in sine)


def test_the_key_holds_names_and_boundaries_and_no_line_numbers(cache_dir):
    args = _arguments()

    def key(**how):
        return tracing.names_key(_made(*how.pop("names", ("alpha", "beta")),
                                       **how).lower(*args))

    assert key() == key(lines_lower=2)
    assert key() != key(names=("gamma", "beta"))
    assert key() != key(moved=True)
    assert len(key()) == 64


def test_the_span_is_recorded_without_a_session_and_the_counter_is_exported(
        cache_dir):
    tracing.recorder().clear()
    _scopes_of("first", _made("alpha", "beta"))
    asked = [s for s in tracing.recorder().spans()
             if s["name"] == "tracing.program_scopes"]
    assert len(asked) == 1
    assert asked[0]["ids"] == {"program": "first", "source": "own_compile"}
    assert asked[0]["end_ns"] > asked[0]["start_ns"]
    tracing.recorder().clear()
    key = 'program_scopes_total{source="own_compile"}'
    assert MetricsRegistry().snapshot()["counters"][key] >= 1
    assert "pbt_" + key in MetricsRegistry().prometheus_text()
    assert key not in MetricsRegistry(enabled=False).snapshot()["counters"]


def test_without_a_cache_directory_a_map_is_read_and_nothing_is_kept(
        cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    scopes = _scopes_of("first", _made("alpha", "beta"))
    assert {"alpha", "beta"} <= _names(scopes)
    assert os.listdir(cache_dir) == []


def test_a_plain_function_is_never_noted_and_stops_no_capture(cache_dir):
    """A test that swaps a served program for a plain function (the
    benchmark's own do) left it in the process's table, and the next
    `device_trace` of that process wrote no file: the driver's PR 34 run."""
    def plain(x):
        return x

    tracing.note_program("plain", plain, (jnp.ones(3),))
    assert "plain" not in tracing.noted_programs()
    assert tracing.program_scopes("plain") is None
