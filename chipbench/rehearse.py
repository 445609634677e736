#!/usr/bin/env python3
"""Compile each cell's round block for a described v5e, without a chip.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named), builds the
``FederatedSimulation`` the harness builds, at the cell's real sizes,
and compiles its round block (``_run_block``, one round) for one chip
of a ``v5e:2x2`` topology.  The TPU compiler refuses here what the chip
would refuse.  Prints, per cell, the compile seconds, the compiled
program's ``memory_analysis`` and the Mosaic kernels it holds
(``tpu_custom_call`` instructions, named as the trace will name them).

The flat path picks its kernels from ``jax.default_backend()``, which
is the CPU here; this script makes it pick the compiled Mosaic kernels,
as it does on the chip.  The block is traced at the configuration's
``matmul_precision``, as a run traces it.
"""
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(names) -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import data as datasets
    from chipbench import harness, precision
    from repro.kernels import ops

    jax.config.update("jax_enable_compilation_cache", False)
    ops.resolve_kernel_mode = lambda interpret=None: (True, False)
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    bench = harness.load_json(harness.ROOT / "BENCHMARK.json")
    for name in names or [w["name"] for w in bench["workloads"]]:
        cell = harness.find_cell(name, bench)
        config = cell["config"]
        data = datasets.load(config["dataset"])
        rec = harness.recipe(config, cell["traffic"], data.counts)
        model = harness.model_module(config)
        params = model.init_params(config["model"], jax.random.key(0))
        sim = harness.build_sim(cell, data, params, rec, harness.init_shared(
            model, config["model"], 0))
        spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=chip), sim.init_state())
        ids = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=chip)
        t0 = time.perf_counter()
        with precision.program(config):
            compiled = sim._run_block.lower(spec, ids).compile()
        secs = time.perf_counter() - t0
        hlo = compiled.as_text()
        m = compiled.memory_analysis()
        calls = re.findall(r"^\s*(\S+) = [^\n]*tpu_custom_call", hlo, re.M)
        gib = 2.0**-30
        print(f"[{name}] S={rec['S']} B={rec['batch_size']} steps="
              f"{rec['steps']} N={config['model']['num_params']:,}: compiled for "
              f"v5e in {secs:.1f} s; arguments {m.argument_size_in_bytes * gib:.3f}"
              f" GiB, outputs {m.output_size_in_bytes * gib:.3f} GiB, "
              f"temporaries {m.temp_size_in_bytes * gib:.3f} GiB, code "
              f"{m.generated_code_size_in_bytes * gib:.3f} GiB; "
              f"Mosaic kernels (tpu_custom_call) {calls}",
              flush=True)
        del sim, compiled, hlo


if __name__ == "__main__":
    main(sys.argv[1:])
