"""Compile every cell's timed program for a described v5e, no chip needed.

    JAX_PLATFORMS=cpu python -m benchmark.rehearse [cell ...]

Prints, per cell, what the chip's own compiler says one device holds
for that program: arguments + outputs + temporaries - aliased. A
compile that passes is not a chip run; the table goes into PERF.md
beside each run's `peak_hbm_gib.*`.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def device_bytes(compiled) -> int:
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)


def compile_cell(name: str, manifest: dict, one_chip):
    import jax

    cell = next(w for w in manifest["workloads"] if w["name"] == name)
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    with open(os.path.join(ROOT, "benchmark", "workloads", name + ".json")) as f:
        workload = json.load(f)
    driver = importlib.import_module("benchmark.drivers." + workload["driver"])
    fn, args, static = driver.cell_program(workload, config)
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), args)
    return fn.lower(*args, **static).compile()


def main(argv=None) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    jax.config.update("jax_enable_compilation_cache", False)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    names = list(argv or sys.argv[1:]) or [w["name"] for w in manifest["workloads"]]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    for name in names:
        compiled = compile_cell(name, manifest, one_chip)
        print(f"{name}: {device_bytes(compiled) / 2 ** 30:.2f} GiB on the device",
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
