"""Child process for the compile-cache warm-boot test (ISSUE 11
satellite): arm the persistent compilation cache where
JAX_COMPILATION_CACHE_DIR says (the one way a run places it), boot a
tiny serve Server (warming two request kinds), and print one JSON line
{"warmup_seconds", "executables"}. Run twice against the SAME fresh
cache dir by tests/test_fleet.py: the first boot compiles cold, the
second deserializes warm executables and must be faster — the number a
restarted fleet replica's boot time rides on.

A separate process per boot is the point: the in-process jit cache
would make a second same-process boot trivially 'warm' without ever
touching the persistent cache.
"""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

os.environ["JAX_PLATFORMS"] = "cpu"


def main() -> int:
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()

    import jax

    from proteinbert_tpu.configs import (
        DataConfig, ModelConfig, OptimizerConfig, PretrainConfig,
        TrainConfig,
    )
    from proteinbert_tpu.serve import Server
    from proteinbert_tpu.train import create_train_state

    cfg = PretrainConfig(
        model=ModelConfig(local_dim=32, global_dim=64, key_dim=16,
                          num_heads=2, num_blocks=2, num_annotations=48,
                          dtype="float32"),
        data=DataConfig(seq_len=64, batch_size=4),
        optimizer=OptimizerConfig(warmup_steps=5),
        train=TrainConfig(seed=0, max_steps=1),
    )
    params = create_train_state(jax.random.PRNGKey(0), cfg).params
    srv = Server(params, cfg, buckets=(32, 64), max_batch=2,
                 cache_size=0, warm_kinds=("embed", "predict_go"))
    srv.start()
    out = {"warmup_seconds": srv.dispatcher.warmup_seconds_total,
           "executables": srv.dispatcher.executable_count}
    srv.drain(timeout=30)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
