"""Record what the decoder's plain reference returns at `glm-tiny`, so
that a rewrite of its host arithmetic is held to the numbers it gave
before (run on the CPU, with the tree whose arithmetic is to be pinned).

    JAX_PLATFORMS=cpu python3 -m benchmark.record_reference_fixture

For each of `CASES` (the cell's own reference: float32, weights rounded
to bfloat16 where they enter products; the two controls one precision
down) `glm4_moe_lite_f32.follow_steps` follows three steps on three
seeded packed batches of 2 x 64 tokens. Kept: the sizes and optimizer it
was called with, the batches, the three losses, every leaf's
`first_grad_norms` and `change_norms`, the first step's chosen experts
and the balance bias. Written to
tests/benchmark/fixtures/glm_reference_tiny.json;
tests/benchmark/test_glm_reference.py holds `follow_steps` to it.
"""

from __future__ import annotations

import json
import os

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(ROOT, "tests", "benchmark", "fixtures",
                       "glm_reference_tiny.json")
SEED = 3000000041
ROWS, LENGTH, STEPS = 2, 64, 3
CASES = {"f32": ("f32", "bf16"), "bf16_params": ("bf16_params", "bf16"),
         "int8": ("int8", "bf16")}       # name: (precision, operands)


def sizes():
    """(c, o) as `drivers/lm_pretrain.py` hands them to the reference."""
    from benchmark.drivers import lm_pretrain
    from benchmark.drivers.pretrain import optimizer_sizes

    with open(os.path.join(ROOT, "benchmark", "workloads",
                           "pretrain-glm47flash-packed8k.json")) as f:
        workload = json.load(f)
    workload.update(workload["rehearsal"])
    with open(os.path.join(ROOT, workload["config_file"])) as f:
        config = json.load(f)
    cfg = lm_pretrain.cell_config(workload, config)
    return lm_pretrain.reference_sizes(config, cfg), optimizer_sizes(config)


def batches(vocab_size: int) -> list:
    """Three packed batches: documents of 3 to 24 tokens laid end to end,
    segment ids from 1, the row's tail left as padding (segment 0)."""
    rng = np.random.default_rng(SEED)
    out = []
    for _ in range(STEPS):
        tokens = rng.integers(0, vocab_size, (ROWS, LENGTH)).astype(np.int32)
        seg = np.zeros((ROWS, LENGTH), np.int32)
        for row in seg:
            at, n = 0, 1
            while at < LENGTH - 8:
                size = int(rng.integers(3, 25))
                row[at:at + size] = n
                at, n = at + size, n + 1
            row[LENGTH - 5:] = 0
        out.append({"tokens": np.where(seg > 0, tokens, 0), "segment_ids": seg})
    return out


def leaf_list(tree) -> list:
    import jax

    return [float(x) for x in jax.tree.leaves(tree)]


def reading(out: dict) -> dict:
    """What the fixture keeps of `follow_steps`' result."""
    return {"losses": [float(x) for x in out["losses"]],
            "first_grad_norms": leaf_list(out["first_grad_norms"]),
            "change_norms": leaf_list(out["change_norms"]),
            "first_ids": np.asarray(out["first_ids"]).tolist(),
            "bias": {k: np.asarray(v).tolist() for k, v in out["bias"].items()}}


def main() -> int:
    from benchmark.reference import glm4_moe_lite_f32 as ref

    c, o = sizes()
    fed = batches(c["vocab_size"])
    record = {"seed": SEED, "c": c, "o": o,
              "batches": [{k: v.tolist() for k, v in b.items()} for b in fed],
              "cases": {}}
    for name, (precision, operands) in CASES.items():
        out = ref.follow_steps(SEED, fed, c, o, precision=precision,
                               operands=operands)
        record["cases"][name] = dict(reading(out), precision=precision,
                                     operands=operands)
        print(name, record["cases"][name]["losses"])
    with open(FIXTURE, "w") as f:
        json.dump(record, f)
    print("wrote", FIXTURE, os.path.getsize(FIXTURE), "bytes")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
