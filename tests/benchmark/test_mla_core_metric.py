"""PR 31's per-layer metric on hand-made observations: `mla_core_device_ms.train`
reads the `mla_core` scope inside `mla` (the attention core: the flash
kernels with the head transposes around them) and finds nothing to read
in a program that has no such scope."""

import json
import os

import pytest

from benchmark.run import _layer_metric

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MS = 1_000_000


def _obs(scopes):
    plane = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ["jit_train_step(1)", 0, 100 * MS], ["jit_train_step(1)", 110 * MS, 100 * MS]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1 = bf16[] fusion()", 0, 10 * MS],               # a projection
            ["%transpose.2 = bf16[] transpose()", 10 * MS, 2 * MS],    # heads first
            ["%segment_flash_fwd.3 = bf16[] custom-call()", 12 * MS, 20 * MS],
            ["%segment_flash_bwd_dkv.4 = bf16[] custom-call()", 40 * MS, 30 * MS],
            ["%fusion.5 = f32[] fusion()", 70 * MS, 6 * MS],
            ["%segment_flash_fwd.3 = bf16[] custom-call()", 122 * MS, 24 * MS]]}]}
    return {"program": "train_step", "scopes": scopes, "trace": {"plane": plane}}


def test_the_new_readers_on_hand_made_observations():
    core = "jvp(forward)/while/mla/mla_core"
    back = "transpose(jvp(forward))/while/rematted_computation/mla/mla_core"
    scoped = {"fusion.1": "jvp(forward)/while/mla", "transpose.2": core,
              "segment_flash_fwd.3": core, "segment_flash_bwd_dkv.4": back,
              "fusion.5": "optimizer"}
    obs = _obs(scoped)
    # two runs: (2 + 20 + 30) and 24 ms under the core's scope
    assert _layer_metric("mla_core_device_ms.train")(obs) == pytest.approx(38.0)
    # `mla` keeps reading the whole, the core inside it
    assert _layer_metric("mla_device_ms.train")(obs) == pytest.approx(43.0)
    # the parent's program: the same operations under `mla` alone
    parent = _obs({k: v.replace("/mla_core", "") for k, v in scoped.items()})
    assert _layer_metric("mla_device_ms.train")(parent) == pytest.approx(43.0)
    assert _layer_metric("mla_core_device_ms.train")(parent) is None
    # no trace, no scope map: nothing to read
    assert _layer_metric("mla_core_device_ms.train")({}) is None
    assert _layer_metric("mla_core_device_ms.train")({"program": "train_step"}) is None


def test_the_manifest_lists_the_core_metric_in_the_decoder_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "mla_core_device_ms.train")     # by name, not by place
    assert entry == {
        "name": "mla_core_device_ms.train", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "Kernels and XLA ops",
        "moves": "train_residues_per_s",
        "workloads": ["pretrain-glm47flash-packed8k"]}
    assert os.path.exists(os.path.join(
        ROOT, "benchmark", "layer_metrics", entry["name"] + ".py"))
