"""Show, on the chip, what `memory_stats()` counts where.

    chiprun -- python3 -m benchmark.memdiag --workload <cell>

`benchmark.device.memory_peak_bytes` reports `peak_bytes_in_use` +
`peak_bytes_reserved`. This is the evidence for that sum: the cell's
timed program is compiled for the chip it runs on, its arguments are
made as zeros, and the runtime's counters are read before the program
is loaded, after its first run and after its second, beside the
compiler's own account of arguments, outputs and temporaries. One JSON
line. What to read from it: `in_use` grows by the arguments and outputs
and never by the temporaries; `reserved` grows by about the temporaries
when the program first runs and stays.
"""

from __future__ import annotations

import argparse
import importlib
import json

FIELDS = ("bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
          "peak_bytes_reserved", "bytes_limit")


def counters(device) -> dict:
    stats = device.memory_stats() or {}
    return {k: int(stats[k]) for k in FIELDS if k in stats}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import run as bench_run
    from benchmark.device import memory_peak_bytes
    from proteinbert_tpu.utils.compat import configure_compile_cache

    configure_compile_cache()
    run = bench_run.tool_run(args.workload, 0, 1.0, args.rehearse)
    device = bench_run._devices(run)[0]
    driver = importlib.import_module("benchmark.drivers." + run.workload["driver"])
    fn, abstract, static = driver.cell_program(run.workload, run.config)

    read = {"before": counters(device)}
    operands = jax.jit(lambda: jax.tree.map(
        lambda a: jnp.zeros(a.shape, a.dtype), abstract))()
    jax.block_until_ready(operands)
    read["operands_made"] = counters(device)
    compiled = fn.lower(*operands, **static).compile()
    ma = compiled.memory_analysis()
    for label in ("first_run", "second_run"):
        # a donated argument (the train state) comes back as an output
        out = compiled(*operands)
        jax.block_until_ready(out)
        if any(x.is_deleted() for x in jax.tree.leaves(operands[0])):
            operands = (out[0],) + tuple(operands[1:])
        del out
        read[label] = counters(device)
    print(json.dumps({
        "workload": args.workload,
        "device": device.device_kind,
        "compiler": {"arguments": int(ma.argument_size_in_bytes),
                     "outputs": int(ma.output_size_in_bytes),
                     "temporaries": int(ma.temp_size_in_bytes),
                     "aliased": int(ma.alias_size_in_bytes)},
        "counters": read,
        "memory_peak_bytes": memory_peak_bytes([device]),
    }))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
