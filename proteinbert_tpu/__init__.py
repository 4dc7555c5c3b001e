"""proteinbert_tpu — a TPU-native (JAX/XLA/Pallas/pjit) ProteinBERT framework.

A ground-up, TPU-first re-design with the full capability surface of the
reference repo Aedelon/ProteinBERT-PyTorch-Replication (surveyed in
/root/repo/SURVEY.md): offline UniRef90+GO ETL, online denoising corruption
pipeline, the dual-track (local sequence / global annotation) ProteinBERT
model, pretraining and fine-tuning engines, checkpoint/resume, and — new in
this build, absent in the reference — a distributed layer (data/tensor/
sequence parallelism over a `jax.sharding.Mesh`), Pallas fused kernels, and a
real test suite.

Package map (≈ reference layer map, SURVEY.md §1):
  configs/   dataclass config system (reference had none — SURVEY §5 "Config")
  data/      online pipeline: vocab, tokenization, corruption, datasets
             (reference ProteinBERT/data_processing.py)
  etl/       offline UniRef90 XML → SQLite → HDF5 pipeline
             (reference ProteinBERT/uniref_dataset.py)
  models/    dual-track model (reference ProteinBERT/modules.py)
  ops/       losses, metrics, conv helpers
  kernels/   Pallas TPU kernels (hot-path fused local-track block)
  parallel/  mesh, sharding rules, sequence parallelism (reference: absent)
  train/     pretrain/fine-tune engines, schedules, checkpointing
             (reference ProteinBERT/utils.py)
  serve/     online inference: continuous micro-batching over length
             buckets, result cache, HTTP endpoint (reference: absent)
  utils/     logging/profiling/task-array utilities
             (reference ProteinBERT/shared_utils/util.py)
  cli/       entry points (reference create_uniref_db.py etc.)
"""

__version__ = "0.1.0"
