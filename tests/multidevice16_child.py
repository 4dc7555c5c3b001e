"""16-virtual-device parity child (VERDICT r4 weak #4 / round-5 item 5).

Every in-suite mesh caps fsdp/model at extent 2 (the pytest process is
pinned to 8 virtual CPU devices at backend init), but off-by-N bugs in
gather/reduce-scatter sharding rules characteristically appear only at
extents >2. This child runs in its OWN process with 16 virtual CPU
devices (utils/compat.request_cpu_devices) and asserts the sharded
step is numerically identical to the single-device step. Cheap
insurance before real-pod day (SURVEY C18/C19; the reference has no
distributed path at all).

Usage: python tests/multidevice16_child.py {fsdp4|model4|sp4-bucketed}
Prints one JSON line with the compared losses.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# Small dims, all divisible by the >2 axis extents below.
MODEL = dict(local_dim=16, global_dim=64, key_dim=16, num_heads=4,
             num_blocks=2, num_annotations=64, dtype="float32")


def _cfg(mesh_cfg, **data_kw):
    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )

    data = dict(seq_len=32, batch_size=16)
    data.update(data_kw)
    return PretrainConfig(
        model=ModelConfig(**MODEL),
        data=DataConfig(**data),
        optimizer=OptimizerConfig(learning_rate=1e-3, warmup_steps=10),
        mesh=mesh_cfg,
        train=TrainConfig(max_steps=2),
    )


def _dense_parity(scenario):
    """fsdp=4 / model=4: sharded train_step vs single-device, same batch
    and init — sharding must not change the math (the 8-device tier's
    test_sharded_train_step_matches_single_device at doubled extents)."""
    import numpy as np

    import jax
    from proteinbert_tpu.configs import MeshConfig
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.synthetic import make_random_proteins
    from proteinbert_tpu.parallel import (
        batch_sharding, make_mesh, shard_train_state,
    )
    from proteinbert_tpu.train import create_train_state, train_step

    mesh_cfg = (MeshConfig(data=2, fsdp=4, model=2) if scenario == "fsdp4"
                else MeshConfig(data=2, fsdp=2, model=4))
    cfg = _cfg(mesh_cfg)
    rng = np.random.default_rng(0)
    seqs, ann = make_random_proteins(
        cfg.data.batch_size, rng, num_annotations=MODEL["num_annotations"],
        max_len=40)
    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)
    batch = next(make_pretrain_iterator(ds, cfg.data.batch_size, seed=0))

    ref_state, ref_m = train_step(
        create_train_state(jax.random.PRNGKey(0), cfg), dict(batch), cfg)

    mesh = make_mesh(mesh_cfg)
    state = shard_train_state(
        create_train_state(jax.random.PRNGKey(0), cfg), mesh)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
    new_state, m = train_step(state, dbatch, cfg)

    ref_loss, got_loss = float(ref_m["loss"]), float(m["loss"])
    assert abs(got_loss - ref_loss) <= 2e-5 * max(1.0, abs(ref_loss)), (
        ref_loss, got_loss)
    max_err = 0.0
    for r, g in zip(jax.tree.leaves(ref_state.params),
                    jax.tree.leaves(new_state.params)):
        err = float(np.max(np.abs(
            np.asarray(r, np.float64)
            - np.asarray(jax.device_get(g), np.float64))))
        max_err = max(max_err, err)
    assert max_err < 2e-5, (scenario, max_err)
    return {"mesh": dict(mesh.shape), "ref_loss": ref_loss,
            "sharded_loss": got_loss, "max_param_err": max_err}


def _sp4_bucketed():
    """data=2 x fsdp=2 x seq=4: mixed-length corpus -> length-bucketed
    lockstep batches -> the EXPLICIT seq-parallel step (halo conv +
    distributed softmax) — every emitted bucket shape must match the
    implicit-SPMD step's loss on the identical batch (the 8-device
    test_long_preset_miniature_h5_bucketed_seq_parallel, with the seq
    axis at 4 alongside a live fsdp axis)."""
    import numpy as np

    import jax
    from proteinbert_tpu.configs import MeshConfig
    from proteinbert_tpu.data import InMemoryPretrainingDataset
    from proteinbert_tpu.data.dataset import make_bucketed_iterator
    from proteinbert_tpu.parallel import make_mesh
    from proteinbert_tpu.parallel.seq_parallel import (
        make_seq_parallel_train_step,
    )
    from proteinbert_tpu.train import create_train_state, train_step

    mesh_cfg = MeshConfig(data=2, fsdp=2, seq=4)
    cfg = _cfg(mesh_cfg, seq_len=128, batch_size=8, buckets=(32, 128))
    rng = np.random.default_rng(0)
    seqs = []
    for i in range(64):
        n = (int(rng.integers(5, 28)) if i % 2
             else int(rng.integers(80, 120)))
        seqs.append("".join(
            rng.choice(list("ACDEFGHIKLMNPQRSTVWY"), size=n)))
    ann = (rng.random((64, MODEL["num_annotations"])) < 0.1)
    ds = InMemoryPretrainingDataset(seqs, ann, cfg.data.seq_len)

    mesh = make_mesh(mesh_cfg)
    sstep = make_seq_parallel_train_step(mesh, cfg)
    it = make_bucketed_iterator(ds, cfg.data.batch_size, cfg.data.buckets,
                                seed=3, num_epochs=1)
    widths, rows = set(), []
    for batch, _ in zip(it, range(4)):
        widths.add(batch["tokens"].shape[1])
        _, ref_m = train_step(
            create_train_state(jax.random.PRNGKey(0), cfg), dict(batch),
            cfg)
        _, sp_m = sstep(
            create_train_state(jax.random.PRNGKey(0), cfg), dict(batch))
        ref_loss, sp_loss = float(ref_m["loss"]), float(sp_m["loss"])
        assert np.isfinite(sp_loss)
        assert abs(sp_loss - ref_loss) <= 1e-4 * max(1.0, abs(ref_loss)), (
            ref_loss, sp_loss)
        rows.append({"L": int(batch["tokens"].shape[1]),
                     "ref_loss": ref_loss, "sp_loss": sp_loss})
    assert widths == {32, 128}, widths  # both buckets actually ran
    return {"mesh": dict(mesh.shape), "buckets": rows}


def main():
    scenario = sys.argv[1]
    import jax

    from proteinbert_tpu.utils.compat import request_cpu_devices

    request_cpu_devices(16)
    assert jax.device_count() == 16, jax.device_count()

    if scenario in ("fsdp4", "model4"):
        out = _dense_parity(scenario)
    elif scenario == "sp4-bucketed":
        out = _sp4_bucketed()
    else:
        raise SystemExit(f"unknown scenario {scenario!r}")
    print(json.dumps({"scenario": scenario, "ok": True, **out}))


if __name__ == "__main__":
    main()
