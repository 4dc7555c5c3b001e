#!/usr/bin/env python
"""Reshard smoke (ISSUE 11, tier-1 stage): save a tiny train state on
one CPU-virtual mesh, reshard it onto another through the real
`pbt reshard` verb (parallel/reshard.reshard_checkpoint), and assert

  - the round trip is byte-identical in the mesh-independent canonical
    form (params AND optimizer state, ZeRO-1 leg included),
  - the collective schedule's wire bytes were counted (same-device-set
    legs report a nonzero 'collective' schedule; the to-single-chip leg
    honestly reports 'host_staged'),
  - the emitted `reshard` events round-trip the schema validator.

Exit nonzero on any violation — this stage GATES (run_tier1.sh).
"""

from __future__ import annotations

import json
import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")


def main() -> int:
    import dataclasses

    import jax

    from proteinbert_tpu.utils.compat import request_cpu_devices

    request_cpu_devices(8)

    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig, save_config,
    )
    from proteinbert_tpu.obs import read_events
    from proteinbert_tpu.parallel.reshard import (
        mesh_from_config, parse_mesh_spec, reshard_checkpoint,
        states_byte_identical, target_template,
    )
    from proteinbert_tpu.train.checkpoint import Checkpointer

    if jax.device_count() < 8:
        print(f"SMOKE SKIP-FAIL: need 8 virtual CPU devices, have "
              f"{jax.device_count()}")
        return 2

    cfg = PretrainConfig(
        model=ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                          num_heads=2, num_blocks=2, num_annotations=32,
                          dtype="float32"),
        data=DataConfig(seq_len=32, batch_size=4),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1),
    )
    cfg42 = cfg.replace(mesh=dataclasses.replace(cfg.mesh, data=4, fsdp=2),
                        parallel=dataclasses.replace(cfg.parallel,
                                                     zero_update=True))
    failures = []
    with tempfile.TemporaryDirectory() as d:
        src = os.path.join(d, "src_4x2")
        mesh42 = mesh_from_config(cfg42.mesh)
        state = target_template(cfg42, mesh42, zero_update=True)
        ck = Checkpointer(src, async_save=False)
        ck.save(0, state, {"batches_consumed": 5})
        ck.close()
        save_config(cfg42, os.path.join(src, "config.json"))
        canonical = target_template(cfg42, None)

        events = os.path.join(d, "events.jsonl")
        # Leg 1 stays on the 8-device set (a real collective schedule);
        # legs 2/3 change the device set (honest host_staged reporting).
        legs = [("8x1", "collective"), ("1", "host_staged"),
                ("4x2", "host_staged")]
        prev = src
        for i, (spec, want_sched) in enumerate(legs):
            dst = os.path.join(d, f"leg{i}_{spec.replace('x', 'by')}")
            from proteinbert_tpu.obs import Telemetry

            tele = Telemetry(events_path=events)
            try:
                out = reshard_checkpoint(
                    prev, dst, target_mesh_cfg=parse_mesh_spec(spec),
                    telemetry=tele)
            finally:
                tele.close()
            print(json.dumps({"leg": f"{prev.split('/')[-1]}->{spec}",
                              **out}))
            if out["parity"] is not True:
                failures.append(f"leg {spec}: parity not verified")
            if out["schedule"] != want_sched:
                failures.append(f"leg {spec}: schedule {out['schedule']} "
                                f"!= expected {want_sched}")
            if want_sched == "collective" \
                    and out["wire_bytes"].get("total", 0) <= 0:
                failures.append(f"leg {spec}: collective schedule with "
                                "zero wire bytes")
            # Mesh-independent canonical parity vs the ORIGINAL state.
            ck = Checkpointer(dst, async_save=False)
            back, data_state = ck.restore(canonical)
            ck.close()
            if data_state != {"batches_consumed": 5}:
                failures.append(f"leg {spec}: data_state lost "
                                f"({data_state})")
            if not states_byte_identical(state, back):
                failures.append(f"leg {spec}: restored state is NOT "
                                "byte-identical to the original")
            prev = dst

        recs = read_events(events, strict=True)
        reshards = [r for r in recs if r["event"] == "reshard"]
        if len(reshards) != len(legs):
            failures.append(f"{len(reshards)} reshard events != "
                            f"{len(legs)} legs")

    if failures:
        print("RESHARD SMOKE FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    print("reshard smoke OK: 4x2 -> 8x1 -> 1 -> 4x2 byte-identical "
          "(ZeRO-1 layout), schedules byte-accounted, events valid",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
