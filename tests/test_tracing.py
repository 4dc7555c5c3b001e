"""The span spine (proteinbert_tpu/obs/tracing.py): when it records, what a
record holds, the compile spans, the instruction -> scope map of the two
hot programs, and the spans of the trainer loop and the serve batch path.
A CPU profiler session is enough to switch recording on."""

import dataclasses
import gzip
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proteinbert_tpu import inference, obs
from proteinbert_tpu.configs import get_preset
from proteinbert_tpu.obs import tracing
from proteinbert_tpu.obs.tracing import SpanCollector, span


@pytest.fixture
def session(tmp_path):
    """A live profiler session with an empty recorder; `stop()` ends it
    (the fixture ends it anyway) and hands back what was recorded."""
    tracing.recorder().clear()
    jax.profiler.start_trace(str(tmp_path / "profile"))
    live = [True]

    def stop():
        if live[0]:
            live[0] = False
            jax.profiler.stop_trace()
        return tracing.recorder().spans()

    yield stop
    stop()
    tracing.recorder().clear()


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


# ------------------------------------------------------------ primitive

def test_nothing_is_recorded_without_a_session_or_a_collector():
    tracing.recorder().clear()
    with span("quiet", batch=1) as s:
        result = 6 * 7
    assert result == 42 and len(tracing.recorder()) == 0
    assert s.end_ns >= s.start_ns and s.seconds >= 0.0
    assert s.seconds == pytest.approx((s.end_ns - s.start_ns) * 1e-9)


def test_the_bodys_exception_passes_through_and_the_span_still_ends():
    col = SpanCollector()
    with pytest.raises(KeyError, match="boom"):
        with span("failing", col) as s:
            raise KeyError("boom")
    assert s.end_ns >= s.start_ns
    assert [r["name"] for r in col.spans()] == ["failing"]


def test_an_explicit_collector_records_without_a_session():
    tracing.recorder().clear()
    tele = obs.Telemetry(metrics=False, spans=True)
    with span("eval_bracket", tele.spans, step=4):
        pass
    (rec,) = tele.spans.spans()
    assert rec["name"] == "eval_bracket" and rec["ids"] == {"step": 4}
    assert len(tracing.recorder()) == 0     # and only where it was told to


def test_a_session_switches_recording_on_and_off(session):
    with span("inside", batch=3):
        pass
    spans = session()
    with span("after"):
        pass
    assert [s["name"] for s in spans] == ["inside"]
    assert [s["name"] for s in tracing.recorder().spans()] == ["inside"]


def test_records_carry_parent_ids_and_the_ids_passed_in(session):
    with span("train.step", step=7) as outer:
        with span("train.dispatch") as inner:
            pass
        with span("serve.place", batch=12, n=3):
            pass
    spans = _by_name(session())
    step, = spans["train.step"]
    dispatch, = spans["train.dispatch"]
    place, = spans["serve.place"]
    assert step["parent"] is None and step["ids"] == {"step": 7}
    assert dispatch["parent"] == step["id"] == place["parent"]
    assert place["ids"] == {"batch": 12, "n": 3}
    assert len({step["id"], dispatch["id"], place["id"]}) == 3
    assert step["tid"] == threading.get_ident()
    assert step["start_ns"] <= dispatch["start_ns"] <= dispatch["end_ns"]
    assert dispatch["end_ns"] <= place["start_ns"] <= step["end_ns"]
    assert (outer.start_ns, outer.end_ns) == (step["start_ns"], step["end_ns"])
    assert inner.seconds == pytest.approx(
        (dispatch["end_ns"] - dispatch["start_ns"]) * 1e-9)


def test_the_ring_is_bounded_and_thread_safe():
    import os
    import sys

    col = SpanCollector(capacity=500)
    workers = 2 * (os.cpu_count() or 4)

    def work(k):
        for i in range(200):
            with span("outer", col, batch=k):
                with span("inner", col, batch=k):
                    pass

    threads = [threading.Thread(target=work, args=(k,))
               for k in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    spans = col.spans()
    assert len(spans) == len(col) == 500    # of 400 x workers recorded
    assert col.dropped == 400 * workers - 500
    col.clear()
    assert len(col) == col.dropped == 0
    assert len({s["id"] for s in spans}) == 500
    outers = {s["id"]: s for s in spans if s["name"] == "outer"}
    for s in spans:
        if s["name"] == "inner" and s["parent"] in outers:
            # a parent is the span that enclosed it ON ITS OWN thread
            assert outers[s["parent"]]["tid"] == s["tid"]
            assert outers[s["parent"]]["ids"] == s["ids"]
    assert all(s["parent"] is None for s in outers.values())


def test_post_hoc_add_links_parents_and_dumps_perfetto(tmp_path):
    col = SpanCollector()
    parent = col.add("serve.request", 100.0, 0.5, tid=77, request_id="r1")
    col.add("serve.queue", 100.1, 0.25, tid=77, parent=parent)
    path = col.dump(str(tmp_path / "spans.json.gz"))
    with gzip.open(path, "rt") as f:
        events = [e for e in json.load(f)["traceEvents"] if e["ph"] == "X"]
    req, queue = events
    assert req["tid"] == queue["tid"] == 77
    assert req["ts"] == pytest.approx(100.0e6) and req["dur"] == 0.5e6
    assert queue["ts"] == pytest.approx(100.1e6) and queue["dur"] == 0.25e6
    assert req["args"]["request_id"] == "r1"
    assert queue["args"]["parent"] == req["args"]["id"] == parent


def test_a_compile_span_is_recorded_on_a_new_shape_only(session):
    @jax.jit
    def double(x):
        return x * 2

    def compiles():
        return len(_by_name(tracing.recorder().spans()).get("jax.compile", ()))

    with span("arms.the.listener"):
        first, same_shape = jnp.arange(5.0), jnp.ones(5)
        second = jnp.arange(6.0)
    start = compiles()
    double(first).block_until_ready()
    once = compiles()
    double(same_shape).block_until_ready()
    again = compiles()
    double(second).block_until_ready()
    assert once > start and again == once and compiles() > again
    recorded = _by_name(session())["jax.compile"]
    assert all(c["end_ns"] > c["start_ns"] for c in recorded)
    double(jnp.arange(7.0)).block_until_ready()     # the session is over
    assert compiles() == len(recorded)


# ------------------------------------------------- programs and scopes

HLO = """HloModule jit_step

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %bitcast.9 = f32[4]{0} bitcast(%p), metadata={op_name="jit(step)/jvp(forward)/while/body/closed_call/checkpoint/local_track/reshape"}
}

ENTRY %main.3 (a: f32[4]) -> f32[4] {
  %a = f32[4]{0} parameter(0)
  %fusion.7 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(step)/jit(main)/transpose(jvp(forward))/while/body/closed_call/checkpoint/rematted_computation/attention/dot_general" source_file="x.py"}
  %fusion.8 = f32[4]{0} fusion(%fusion.7), kind=kLoop, calls=%fused_computation.1
  %copy-start.2 = (f32[4]{0}, f32[4]{0:S(1)}, u32[]) copy-start(%fusion.7)
  %copy-done.2 = f32[4]{0:S(1)} copy-done(%copy-start.2)
  ROOT %add.1 = f32[4]{0} add(%fusion.8, %copy-done.2), metadata={op_name="jit(step)/optimizer/add"}
}
"""


def test_scope_path_strips_the_wrappers_and_keeps_the_backward_mark():
    assert tracing.scope_path(
        "jit(step)/transpose(jvp(forward))/while/body/closed_call/checkpoint/"
        "attention/dot_general") == "transpose(jvp(forward))/while/attention"
    assert tracing.scope_path("jit(step)/optimizer/sub") == "optimizer"
    assert tracing.scope_path("reduce_sum") == ""


def test_scopes_from_hlo_names_call_sites_and_borrows_for_the_rest():
    scopes = tracing.scopes_from_hlo(HLO)
    backward = "transpose(jvp(forward))/while/rematted_computation/attention"
    assert scopes["fusion.7"] == backward
    assert scopes["fusion.8"] == "jvp(forward)/while/local_track"  # callee's
    assert scopes["copy-start.2"] == scopes["copy-done.2"] == backward
    assert scopes["add.1"] == "optimizer"
    assert "a" not in scopes


def _names(paths):
    import re

    return {n for p in paths for n in re.findall(r"[A-Za-z_]\w*", p)}


# Two loops as the two compilers write them: the CPU's names its trips
# on the `while`, the TPU's leaves them to the condition's constant. In
# the first a parameter travels (a slice of a (12, 9, 64, 64) stack,
# gathered asynchronously: the start's result is (operand, result)); in
# the second the batch (24 rows global, 6 a chip) is brought together.
CENSUS_HLO = """
HloModule jit_step

%sum (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add = f32[] add(%a, %b)
}

%cond.tpu (p: (s32[], bf16[6,32,64])) -> pred[] {
  %p = (s32[], bf16[6,32,64]) parameter(0)
  %i = s32[]{:T(128)} get-tuple-element(%p), index=0
  %n = s32[]{:T(128)} constant(12)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%body.tpu (p.1: (s32[], bf16[6,32,64])) -> (s32[], bf16[6,32,64]) {
  %p.1 = (s32[], bf16[6,32,64]) parameter(0)
  %x = bf16[6,32,64]{2,1,0:T(8,128)(2,1)} get-tuple-element(%p.1), index=1
  %ag = bf16[24,32,64]{2,1,0:T(8,128)(2,1)} all-gather(%x), channel_id=1, dimensions={0}
  %ar = (f32[24,64]{1,0}, f32[64]{0}) all-reduce(%y, %z), channel_id=2, to_apply=%sum
  ROOT %t = (s32[], bf16[6,32,64]) tuple(%i.1, %x)
}

%cond.cpu (q: (s32[], f32[9,64,16])) -> pred[] {
  %q = (s32[], f32[9,64,16]) parameter(0)
  ROOT %lt.1 = pred[] compare(%j, %m), direction=LT
}

%body.cpu (q.1: (s32[], f32[9,64,16])) -> (s32[], f32[9,64,16]) {
  %q.1 = (s32[], f32[9,64,16]) parameter(0)
  %w = f32[1,9,64,16]{3,2,1,0} get-tuple-element(%q.1), index=1
  %ags = (f32[1,9,64,16]{3,2,1,0}, f32[1,9,64,64]{3,2,1,0}) all-gather-start(%w), channel_id=3, dimensions={3}
  %agd = f32[1,9,64,64]{3,2,1,0} all-gather-done(%ags)
  ROOT %t.1 = (s32[], f32[9,64,16]) tuple(%j.1, %w)
}

ENTRY %main (s: f32[12,9,64,16], b: bf16[6,32,64]) -> f32[] {
  %w.1 = (s32[], bf16[6,32,64]) while(%init), condition=%cond.tpu, body=%body.tpu
  %w.2 = (s32[], f32[9,64,16]) while(%init.1), condition=%cond.cpu, body=%body.cpu, backend_config={"known_trip_count":{"n":"5"}}
  ROOT %loss = f32[] all-reduce(%l), channel_id=4, to_apply=%sum
}
"""


def test_collective_census_counts_by_result_and_by_trip():
    census = tracing.collective_census(
        CENSUS_HLO, rows=24, parameter_shapes=[(12, 9, 64, 64), (12, 64)])
    assert census["by_kind"] == {
        "all-gather": {"count": 12 + 5,
                       "bytes": 12 * 24 * 32 * 64 * 2 + 5 * 9 * 64 * 64 * 4},
        "all-reduce": {"count": 12 + 1, "bytes": 12 * (24 * 64 + 64) * 4 + 4}}
    assert census["activation"] == 24       # the gather and the reduce, 12 trips
    assert census["activation_shapes"] == [[24, 32, 64], [24, 64]]
    assert census["parameter"] == census["parameter_gathers"] == 5
    assert census["bytes"] == sum(
        kind["bytes"] for kind in census["by_kind"].values())
    # The same module under another batch size: nothing has that many rows.
    assert tracing.collective_census(CENSUS_HLO, 48, [])["activation"] == 0


def test_program_scopes_of_the_tiny_train_step_name_every_scope():
    from proteinbert_tpu.train import train_state as ts

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(cfg, model=dataclasses.replace(
        cfg.model, remat=True, remat_policy="convs"))
    state = jax.eval_shape(
        lambda: ts.create_train_state(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((4, 64), jnp.int32),
             "annotations": jax.ShapeDtypeStruct(
                 (4, cfg.model.num_annotations), jnp.float32)}
    assert tracing.program_scopes("never_noted") is None
    tracing.note_program("tiny_train_step", ts.train_step,
                         (state, batch, cfg))
    assert "tiny_train_step" in tracing.noted_programs()
    scopes = tracing.program_scopes("tiny_train_step")
    paths = set(scopes.values())
    assert {"corrupt", "forward", "loss", "optimizer", "step_metrics",
            "embed", "local_track", "attention", "global_track", "heads",
            "while"} <= _names(paths)
    backward = {p for p in paths if "transpose(jvp(" in p}
    assert {"local_track", "attention"} <= _names(backward)
    assert any("rematted_computation" in p for p in backward)
    # the wrappers are gone from every path
    assert not _names(paths) & {"jit", "closed_call", "checkpoint", "body"}


def test_program_scopes_of_the_packed_encode_batch_name_encode_and_pool():
    from proteinbert_tpu.models import proteinbert

    cfg = get_preset("tiny").model
    params = jax.eval_shape(
        lambda: proteinbert.init(jax.random.PRNGKey(0), cfg))
    grid = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    ann = jax.ShapeDtypeStruct((2, 4, cfg.num_annotations), jnp.float32)
    tracing.note_program("tiny_packed", inference._packed_encode_batch,
                         (params, grid, grid, ann, cfg))
    paths = set(tracing.program_scopes("tiny_packed").values())
    assert {"encode", "pool", "local_track", "attention"} <= _names(paths)
    # the scan's body is inside `encode`, whole
    assert all(p.startswith("encode/while") for p in paths if "while" in p)


def test_note_program_keeps_shapes_not_arrays_and_only_the_first_call():
    x = jnp.ones((3, 5))
    tracing.note_program("noted_once", jax.jit(lambda a, k: a * k),
                         (x,), {"k": 2.0})
    tracing.note_program("noted_once", None, ())       # ignored
    _, args, kwargs = tracing._programs["noted_once"]
    assert isinstance(args[0], jax.ShapeDtypeStruct)
    assert args[0].shape == (3, 5) and args[0].sharding == x.sharding
    assert kwargs == {"k": 2.0}
    assert tracing.program_scopes("noted_once") is not None


def test_device_trace_writes_the_spans_and_the_scopes_beside_the_xplane(
        tmp_path):
    import glob
    import os

    from proteinbert_tpu.utils.profiling import device_trace

    step = jax.jit(lambda a: jnp.tanh(a).sum(), inline=False)
    x = jnp.ones((8, 8))
    with span("before.the.capture"):
        pass
    with device_trace(str(tmp_path)):
        tracing.note_program("traced_step", step, (x,))
        with span("train.step", step=1):
            step(x).block_until_ready()
    (plane,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                             / "*.xplane.pb"))
    beside = os.path.dirname(plane)
    with gzip.open(os.path.join(beside, "host_spans.json.gz"), "rt") as f:
        names = [e["name"] for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X"]
    assert "train.step" in names and "before.the.capture" not in names
    with open(os.path.join(beside, "program_scopes.json")) as f:
        scopes = json.load(f)
    assert "traced_step" in scopes and scopes["traced_step"]


def test_device_trace_never_masks_the_bodys_error(tmp_path, monkeypatch):
    import glob

    from proteinbert_tpu.utils.profiling import device_trace

    def broken(name):
        raise RuntimeError("the scope map's compile failed")

    step = jax.jit(lambda a: a + 1)
    monkeypatch.setattr(tracing, "program_scopes", broken)
    monkeypatch.setitem(tracing._programs, "broken_step", (step, (), {}))
    with pytest.raises(KeyError, match="the body's own"):
        with device_trace(str(tmp_path / "a")):
            raise KeyError("the body's own")
    with device_trace(str(tmp_path / "b")):     # and alone it only warns
        step(jnp.ones(3)).block_until_ready()
    assert glob.glob(str(tmp_path / "b" / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))


# ----------------------------------------------------------- hot paths

def test_three_trainer_steps_emit_the_loop_spans_in_order(session):
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.synthetic import make_random_proteins
    from proteinbert_tpu.train.trainer import pretrain

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, seq_len=64, batch_size=4),
        train=dataclasses.replace(cfg.train, max_steps=3, log_every=2))
    seqs, ann = make_random_proteins(
        16, np.random.default_rng(0),
        num_annotations=cfg.model.num_annotations)
    ds = InMemoryPretrainingDataset(seqs, ann, 64)
    tele = obs.Telemetry()
    pretrain(cfg, make_pretrain_iterator(ds, 4, seed=0), telemetry=tele)
    spans = session()
    named = _by_name(spans)
    steps = sorted(named["train.step"], key=lambda s: s["start_ns"])
    assert [s["ids"] for s in steps] == [{"step": 1}, {"step": 2},
                                         {"step": 3}]
    for step in steps:
        inside = sorted((s for s in spans if s["parent"] == step["id"]),
                        key=lambda s: s["start_ns"])
        names = [s["name"] for s in inside]
        assert names[:3] == ["train.data_wait", "train.put",
                             "train.dispatch"], names
        assert all(step["start_ns"] <= s["start_ns"]
                   and s["end_ns"] <= step["end_ns"] for s in inside)
        # log cadence 2: the fetch is in step 2 alone
        assert ("train.log_fetch" in names) == (step["ids"]["step"] == 2)
    # one clock: the gauge at the last log point (step 2) is the spans' sum
    waits = [s for s in named["train.data_wait"]
             if s["parent"] in (steps[0]["id"], steps[1]["id"])]
    assert tele.metrics.snapshot()["gauges"]["data_wait_seconds"] == \
        pytest.approx(sum((s["end_ns"] - s["start_ns"]) * 1e-9
                          for s in waits))
    # the producer thread's own spans, on another thread
    assert named["data.produce"]
    assert {s["tid"] for s in named["data.produce"]} \
        != {steps[0]["tid"]}
    # the trainer noted its step program under the jitted function's name
    assert "train_step" in tracing.noted_programs()


SERVE_SPANS = ("serve.ingest", "serve.assemble", "serve.wait_slot",
               "serve.place", "serve.launch", "serve.fetch",
               "serve.fan_out", "serve.seal")


def test_one_ragged_batch_emits_the_eight_spans_under_one_batch(session):
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(cfg, data=dataclasses.replace(
        cfg.data, seq_len=64, buckets=(16, 32, 64)))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params
    seen = []

    class Capture(obs.Telemetry):
        def emit(self, event, **fields):
            if event in ("serve_request", "serve_batch"):
                seen.append((event, fields))

    srv = Server(params, cfg, max_batch=2, max_wait_s=60.0, cache_size=0,
                 warm_kinds=(), serve_mode="ragged", pipeline_depth=2,
                 telemetry=Capture(metrics=False), trace_sample_rate=1.0)
    futs = [srv.submit("embed", s) for s in ("MKTAYIAKQR", "MKV", "GAVLIM")]
    srv.queue.close()
    while srv.scheduler.poll():
        pass
    for f in futs:
        f.result(timeout=30)
    srv.drain(timeout=30)
    named = _by_name(session())
    for name in SERVE_SPANS + ("serve.retire",):
        assert len(named[name]) == 1, (name, len(named.get(name, ())))
    (batch,) = {s["ids"]["batch"] for n in SERVE_SPANS for s in named[n]}
    assert named["serve.ingest"][0]["ids"]["n"] == 3
    # three short requests share one packed row: the smallest row class
    for name in ("serve.assemble", "serve.launch"):
        ids = named[name][0]["ids"]
        assert (ids["rows"], ids["cls"]) == (1, 1), name
    order = [named[n][0] for n in SERVE_SPANS]
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(order, order[1:]))
    retire = named["serve.retire"][0]
    assert all(named[n][0]["parent"] == retire["id"]
               for n in ("serve.fetch", "serve.fan_out", "serve.seal"))
    # the riders' RequestTrace, and the batch's event, carry the number
    requests = [f for e, f in seen if e == "serve_request"]
    assert len(requests) == 3
    assert {r["batch"] for r in requests} == {batch}
    assert [f["batch"] for e, f in seen if e == "serve_batch"] == [batch]
    assert "_packed_encode_batch" in tracing.noted_programs()


def test_the_bucketed_path_carries_the_same_spans(session):
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state

    cfg = get_preset("tiny")
    cfg = dataclasses.replace(cfg, data=dataclasses.replace(
        cfg.data, seq_len=64, buckets=(16, 32, 64)))
    params = create_train_state(jax.random.PRNGKey(0), cfg).params
    srv = Server(params, cfg, max_batch=4, max_wait_s=60.0, cache_size=0,
                 warm_kinds=(), serve_mode="bucketed")
    futs = [srv.submit("embed", s) for s in ("MKTAYIAKQR", "MKVLAAGIC")]
    srv.queue.close()
    while srv.scheduler.poll():
        pass
    for f in futs:
        f.result(timeout=30)
    srv.drain(timeout=30)
    named = _by_name(session())
    for name in SERVE_SPANS:
        if name != "serve.fan_out":     # rows are split in the seal loop
            assert len(named[name]) == 1, name
    assert len({named[n][0]["ids"]["batch"] for n in SERVE_SPANS
                if n != "serve.fan_out"}) == 1
    # the span's own duration is what pipeline_stats reports
    retire = named["serve.retire"][0]
    assert srv.scheduler.pipeline_stats()["finalize_seconds_total"] \
        == pytest.approx((retire["end_ns"] - retire["start_ns"]) * 1e-9,
                         abs=1e-6)
