"""The span spine's start-up collector (obs/tracing.startup_spans): what it
takes before a process's first profiler session, that it is sealed for
good by that session or its bound, and what the program's own `startup.*`
spans hold."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.obs import tracing
from proteinbert_tpu.obs.tracing import span, startup_span

STARTUP_NAMES = {"jax.compile", "jax.cache_load", "jax.lower", "jax.trace",
                 "startup.backend", "startup.warmup", "startup.init_state",
                 "startup.restore", "startup.first_step"}


@pytest.fixture
def startup(monkeypatch):
    """The collector as a new process has it: open and empty (this
    process has long had its first profiler session or filled it)."""
    tracing.arm()
    tracing._STARTUP.clear()
    monkeypatch.setattr(tracing, "_startup_open", True)
    monkeypatch.setattr(tracing, "_backend_up", False)
    yield tracing.startup_spans
    tracing._STARTUP.clear()
    tracing._seal_startup()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


def _compile_something(width):
    """A jitted function no other test compiles, at a width of its own."""
    @jax.jit
    def startup_probe(x):
        return jnp.tanh(x @ x.T).sum()

    return startup_probe(jnp.ones((width, 3))).block_until_ready()


def test_a_compile_before_any_session_is_recorded_with_its_program(startup):
    _compile_something(5)
    named = _by_name(startup())
    assert set(named) <= STARTUP_NAMES
    ours = [c for c in named["jax.compile"]
            if c["ids"]["program"] == "jit(startup_probe)"]
    assert len(ours) == 1 and ours[0]["ids"]["cached"] in (0, 1)
    assert ours[0]["end_ns"] > ours[0]["start_ns"]
    lowered = [s for s in named["jax.lower"]
               if s["ids"]["program"] == "jit(startup_probe)"]
    assert len(lowered) == 1 and lowered[0]["end_ns"] <= ours[0]["start_ns"]
    # a trace is a record only from a millisecond up
    assert all(s["end_ns"] - s["start_ns"] >= 1e6
               for s in named.get("jax.trace", ()))
    # nothing of it went to the window's recorder
    assert tracing.recorder().spans() == [] or all(
        s["ids"].get("program") != "jit(startup_probe)"
        for s in tracing.recorder().spans())


def test_a_hot_loops_span_never_goes_to_the_start_up_collector(startup):
    with span("train.data_wait"):
        pass
    with span("serve.launch", batch=1):
        pass
    with startup_span("startup.warmup", cls=4, kind="embed") as warmed:
        pass
    records = startup()
    assert [s["name"] for s in records] == ["startup.warmup"]
    assert records[0]["ids"] == {"cls": 4, "kind": "embed"}
    # the span's seconds are the record's: no second clock
    assert warmed.seconds == pytest.approx(
        (records[0]["end_ns"] - records[0]["start_ns"]) * 1e-9)


def test_the_first_device_trace_seals_it_for_good(startup, tmp_path):
    from proteinbert_tpu.utils.profiling import device_trace

    _compile_something(6)
    before = startup()
    assert before
    with device_trace(str(tmp_path / "profile")):
        with span("train.step", step=1):
            _compile_something(7)       # the session's: the recorder's
    inside = [s for s in tracing.recorder().spans() if s["name"] == "jax.compile"]
    assert any(s["ids"]["program"] == "jit(startup_probe)" for s in inside)
    tracing.recorder().clear()
    _compile_something(8)               # after the session: nobody's
    with startup_span("startup.warmup", cls=1, kind="embed"):
        pass
    assert startup() == before
    assert tracing._startup_sink() is None


def test_it_is_bounded_and_keeps_its_oldest_records(startup, monkeypatch):
    monkeypatch.setattr(tracing, "STARTUP_CAPACITY", 3)
    for n in range(5):
        with startup_span("startup.warmup", cls=n, kind="embed"):
            pass
    assert [s["ids"]["cls"] for s in startup()] == [0, 1, 2]
    _compile_something(9)
    assert len(startup()) == 3
    assert tracing._STARTUP.dropped == 0


def test_the_backend_span_is_the_first_touch_alone(startup):
    devices = tracing.backend()
    assert tracing.backend() == devices == jax.devices()
    (up,) = [s for s in startup() if s["name"] == "startup.backend"]
    assert up["ids"] == {"platform": "cpu", "devices": len(devices)}


def test_the_trainers_first_step_holds_its_compile(startup):
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.synthetic import make_random_proteins
    from proteinbert_tpu.train.trainer import pretrain

    cfg = get_preset("tiny")
    # a width no other test trains at: this process compiles or loads it now
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, seq_len=48, batch_size=2),
        train=dataclasses.replace(cfg.train, max_steps=2, log_every=0))
    seqs, ann = make_random_proteins(
        8, np.random.default_rng(0), num_annotations=cfg.model.num_annotations)
    ds = InMemoryPretrainingDataset(seqs, ann, 48)
    pretrain(cfg, make_pretrain_iterator(ds, 2, seed=0))
    named = _by_name(startup())
    (first,) = named["startup.first_step"]
    (made,) = named["startup.init_state"]
    assert made["end_ns"] <= first["start_ns"]
    steps = [c for c in named["jax.compile"]
             if c["ids"]["program"] == "jit(train_step)"]
    assert len(steps) == 1 and steps[0]["parent"] == first["id"]
    assert first["start_ns"] <= steps[0]["start_ns"] \
        and steps[0]["end_ns"] <= first["end_ns"]
    if steps[0]["ids"]["cached"]:       # a load has its retrieval inside
        assert any(s["parent"] == first["id"] for s in named["jax.cache_load"])
    assert "startup.restore" not in named      # no checkpointer, no restore


def test_a_first_step_that_raises_still_closes_its_span(startup):
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.synthetic import make_random_proteins
    from proteinbert_tpu.train.trainer import pretrain

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, seq_len=48, batch_size=2),
        train=dataclasses.replace(cfg.train, max_steps=2, log_every=0))
    seqs, ann = make_random_proteins(
        8, np.random.default_rng(0), num_annotations=cfg.model.num_annotations)
    ds = InMemoryPretrainingDataset(seqs, ann, 48)

    def one_annotation_short():
        for batch in make_pretrain_iterator(ds, 2, seed=0):
            yield dict(batch, annotations=batch["annotations"][:, :-1])

    with pytest.raises((TypeError, ValueError)):
        pretrain(cfg, one_annotation_short())
    (first,) = _by_name(startup())["startup.first_step"]
    assert first["end_ns"] > first["start_ns"]
    assert getattr(tracing._tls, "open", None) is None
    with startup_span("startup.warmup", cls=1, kind="embed"):
        pass
    assert startup()[-1]["parent"] is None      # nothing was left open


def test_the_dispatchers_warmup_is_one_span_a_class_and_kind(startup):
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(cfg, data=dataclasses.replace(
        cfg.data, seq_len=64, buckets=(16, 32, 64)))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params
    srv = Server(params, cfg, max_batch=4, max_wait_s=60.0, cache_size=0,
                 warm_kinds=("embed",), serve_mode="ragged")
    srv.start()
    try:
        warm = _by_name(startup())["startup.warmup"]
        classes = list(srv.dispatcher.batch_classes)
        assert sorted(s["ids"]["cls"] for s in warm) == sorted(classes)
        assert {s["ids"]["kind"] for s in warm} == {"embed"}
        spent = sum((s["end_ns"] - s["start_ns"]) * 1e-9 for s in warm)
        assert 0 < spent <= srv.dispatcher.warmup_seconds_total
        # the compiles the warm-up paid nest under their class's span
        ids = {s["id"] for s in warm}
        assert any(c["parent"] in ids for c in _by_name(startup())["jax.compile"])
    finally:
        srv.drain(timeout=30)
