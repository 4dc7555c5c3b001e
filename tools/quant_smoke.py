#!/usr/bin/env python
"""Quantized-collectives + int8-serving smoke (ISSUE 12, tier-1 stage).

One tiny model on an 8-device CPU-virtual 4x2 mesh, three gates:

  1. TRAIN PARITY — two int8-reduction ZeRO-1 steps vs the replicated
     fp32 reference on the same batch: step-1 loss identical (same
     corruption ops, same key), final param deviation within the
     documented quantization bounds (int8 <= 1e-3, bf16 <= 5e-4,
     nonzero — rounding really happened), the fp32-PAYLOAD explicit
     control within 1e-6 (isolates harness error from quantization
     error), and the int8 step bit-DETERMINISTIC across two runs from
     the same state (the multi-host-lockstep property: noise is a pure
     function of the replicated step key + replica index).
  2. WIRE BYTES — grad-reduction wire bytes of the compiled int8 step
     <= 0.30x the fp32-payload explicit reduce-scatter's, counted from
     the compiled HLO (zero.collective_wire_bytes_from_hlo: output
     shapes + replica_groups — never inferred from source dtypes).
  3. SERVE PARITY — a quant=int8 server (weight-only int8 executables,
     fp32 parity shadow every batch) vs a fp32 server on identical
     requests: per-request deviation within the documented 0.15 bound,
     live parity sampling recorded, quantized trunk weight bytes
     <= 0.40x fp32, and the emitted serve events (with their `quant`
     fields) schema-valid.

Exit nonzero on any violation — this stage GATES (run_tier1.sh).
"""

from __future__ import annotations

import os
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ.setdefault("JAX_PLATFORMS", "cpu")

INT8_PARAM_BOUND = 1e-3   # docs/distributed.md, quantized reduction
BF16_PARAM_BOUND = 5e-4
CONTROL_BOUND = 1e-6      # fp32-payload explicit harness
SERVE_PARITY_BOUND = 0.15  # docs/serving.md, int8 arm
WEIGHT_RATIO_BOUND = 0.40  # tiny dims; large dims approach 0.26
WIRE_RATIO_BOUND = 0.30   # ROADMAP item 1 acceptance


def main() -> int:
    import numpy as np

    from proteinbert_tpu.utils.compat import request_cpu_devices

    request_cpu_devices(8)
    import jax

    from proteinbert_tpu.configs import (
        DataConfig, MeshConfig, ModelConfig, OptimizerConfig,
        ParallelConfig, PretrainConfig, TrainConfig,
    )
    from proteinbert_tpu.data import (
        InMemoryPretrainingDataset, make_pretrain_iterator,
    )
    from proteinbert_tpu.data.vocab import ALPHABET
    from proteinbert_tpu.obs import Telemetry, read_events
    from proteinbert_tpu.parallel import (
        batch_sharding, make_mesh, make_zero_train_step,
        shard_train_state,
    )
    from proteinbert_tpu.parallel.quant import make_quant_zero_train_step
    from proteinbert_tpu.parallel.sharding import state_sharding
    from proteinbert_tpu.parallel.zero import (
        collective_wire_bytes_from_hlo, grad_reduce_wire_bytes,
    )
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state, train_step

    failures = []

    def gate(ok: bool, msg: str) -> None:
        print(("PASS " if ok else "FAIL ") + msg)
        if not ok:
            failures.append(msg)

    mesh_cfg = MeshConfig(data=4, fsdp=2)
    model = ModelConfig(local_dim=16, global_dim=32, key_dim=8,
                        num_heads=4, num_blocks=2, num_annotations=64,
                        dtype="float32")

    def cfg_for(parallel):
        return PretrainConfig(
            model=model,
            data=DataConfig(seq_len=32, batch_size=16),
            optimizer=OptimizerConfig(learning_rate=1e-3,
                                      warmup_steps=10),
            mesh=mesh_cfg, parallel=parallel,
            train=TrainConfig(max_steps=2))

    rng = np.random.default_rng(0)
    alphabet = np.array(list(ALPHABET))
    seqs = ["".join(rng.choice(alphabet, size=int(n)))
            for n in rng.integers(10, 30, size=16)]
    ann = (rng.random((16, 64)) < 0.05).astype(np.float32)
    ds = InMemoryPretrainingDataset(seqs, ann, 32)
    batch = next(make_pretrain_iterator(ds, 16, seed=0))

    # ---- 1. train parity -------------------------------------------
    ref_cfg = cfg_for(ParallelConfig())
    ref = create_train_state(jax.random.PRNGKey(0), ref_cfg)
    ref, rm1 = train_step(ref, dict(batch), ref_cfg)
    ref, _ = train_step(ref, dict(batch), ref_cfg)

    mesh = make_mesh(mesh_cfg)
    bsh = batch_sharding(mesh)
    dbatch = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}

    def two_steps(step, cfg):
        st = shard_train_state(
            create_train_state(jax.random.PRNGKey(0), cfg), mesh,
            zero_update=True)
        st, m1 = step(st, dbatch)
        st, _ = step(st, dbatch)
        return st, m1

    def param_dev(st):
        worst = 0.0
        for r, g in zip(jax.tree.leaves(ref.params),
                        jax.tree.leaves(st.params)):
            worst = max(worst, float(np.max(np.abs(
                np.asarray(r, np.float64)
                - np.asarray(jax.device_get(g), np.float64)))))
        return worst

    int8_cfg = cfg_for(ParallelConfig(zero_update=True,
                                      grad_reduce_dtype="int8"))
    st8, m8 = two_steps(make_zero_train_step(mesh, int8_cfg), int8_cfg)
    dev8 = param_dev(st8)
    gate(abs(float(m8["loss"]) - float(rm1["loss"])) <= 2e-5,
         f"int8 step-1 loss matches fp32 reference "
         f"(d={abs(float(m8['loss']) - float(rm1['loss'])):.2e})")
    gate(0.0 < dev8 <= INT8_PARAM_BOUND,
         f"int8 2-step param deviation {dev8:.2e} within "
         f"(0, {INT8_PARAM_BOUND}]")

    bf_cfg = cfg_for(ParallelConfig(zero_update=True,
                                    grad_reduce_dtype="bf16"))
    stb, _ = two_steps(make_zero_train_step(mesh, bf_cfg), bf_cfg)
    devb = param_dev(stb)
    gate(0.0 < devb <= BF16_PARAM_BOUND,
         f"bf16 2-step param deviation {devb:.2e} within "
         f"(0, {BF16_PARAM_BOUND}]")

    ctrl_step = make_quant_zero_train_step(mesh, int8_cfg,
                                           payload="fp32")
    stc, _ = two_steps(ctrl_step, int8_cfg)
    devc = param_dev(stc)
    gate(devc <= CONTROL_BOUND,
         f"fp32-payload explicit control deviation {devc:.2e} <= "
         f"{CONTROL_BOUND}")

    st8b, _ = two_steps(make_zero_train_step(mesh, int8_cfg), int8_cfg)
    identical = all(
        np.array_equal(np.asarray(jax.device_get(a)),
                       np.asarray(jax.device_get(b)))
        for a, b in zip(jax.tree.leaves(st8.params),
                        jax.tree.leaves(st8b.params)))
    gate(identical, "int8 stochastic rounding is deterministic "
                    "(same state key -> bit-identical params)")

    # ---- 2. wire bytes from compiled HLO ---------------------------
    abstract = jax.eval_shape(
        lambda: create_train_state(jax.random.PRNGKey(0), int8_cfg))
    sh = state_sharding(mesh, abstract, zero_update=True)
    st_abs = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, sh)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bsh[k])
        for k, v in batch.items()}

    def reduce_wire(step):
        hlo = step.lower(st_abs, batch_abs).compile().as_text()
        return grad_reduce_wire_bytes(
            collective_wire_bytes_from_hlo(hlo, mesh.size))

    wire8 = reduce_wire(make_zero_train_step(mesh, int8_cfg))
    wire32 = reduce_wire(ctrl_step)
    ratio = wire8 / max(wire32, 1)
    gate(ratio <= WIRE_RATIO_BOUND,
         f"int8 grad-reduction wire bytes {wire8} <= "
         f"{WIRE_RATIO_BOUND}x fp32 reduce-scatter {wire32} "
         f"(ratio {ratio:.3f})")

    # ---- 3. quantized serve arm ------------------------------------
    serve_cfg = PretrainConfig(
        model=model, data=DataConfig(seq_len=64, batch_size=4))
    params = create_train_state(jax.random.PRNGKey(1), serve_cfg).params
    reqs = ["".join(rng.choice(alphabet, size=int(n)))
            for n in rng.integers(8, 50, size=12)]
    events_path = os.path.join(
        tempfile.mkdtemp(prefix="pbt_quant_smoke_"), "events.jsonl")
    tele = Telemetry(events_path=events_path)
    fp32_srv = Server(params, serve_cfg, max_batch=4, max_wait_s=0.005)
    q_srv = Server(params, serve_cfg, max_batch=4, max_wait_s=0.005,
                   quant="int8", quant_parity_every=1, telemetry=tele)
    with fp32_srv, q_srv:
        worst = 0.0
        for s in reqs:
            a = fp32_srv.embed(s, timeout=120)
            b = q_srv.embed(s, timeout=120)
            for k in a:
                worst = max(worst, float(np.max(np.abs(a[k] - b[k]))))
        stats = q_srv.stats()
    tele.close()
    q = stats["quant"] or {}
    gate(worst <= SERVE_PARITY_BOUND,
         f"int8-arm per-request parity {worst:.4f} <= "
         f"{SERVE_PARITY_BOUND} vs the fp32 arm")
    gate(bool(q.get("parity_samples")),
         f"live parity shadow sampled "
         f"{q.get('parity_samples', 0)} batch(es)")
    gate(q.get("weight_bytes_ratio", 1.0) <= WEIGHT_RATIO_BOUND,
         f"quantized trunk weight bytes ratio "
         f"{q.get('weight_bytes_ratio')} <= {WEIGHT_RATIO_BOUND}")
    recs = read_events(events_path, strict=True)  # raises on invalid
    quant_tagged = [r for r in recs if r.get("quant") == "int8"]
    gate(len(quant_tagged) > 0,
         f"{len(quant_tagged)} schema-valid event(s) carry "
         f"quant='int8' ({len(recs)} total)")

    if failures:
        print(f"\nquant smoke: {len(failures)} gate(s) FAILED")
        return 1
    print("\nquant smoke: all gates passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
