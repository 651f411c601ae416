"""Compile a cell's verify step for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py --workload <cell>

Builds the cell's engine on weight shapes placed on one chip of a
described ``v5e:2x2`` and compiles the step that ``serve`` dispatches at
the cell's ``max_batch``, ``max_len`` and pool, with the paged kernels
compiled through Mosaic (the kernels' backend is steered to ``tpu`` in
this process only; on the CPU they would otherwise be interpreted).
Prints ``memory_analysis()`` and the number of ``tpu_custom_call`` sites.
A compile is not a chip run: nothing here is a time or a measurement.
"""
import argparse
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    import repro.kernels
    repro.kernels.resolve_backend = lambda: "tpu"
    from repro.configs import get_config
    from repro.core.heads import init_draft_params
    from repro.launch.serve import build_engine
    from repro.models.model import init_params

    from harness import cell as run_cell
    bench = run_cell.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = run_cell.find_cell(bench, args.workload)
    conf = run_cell.config_file(bench, w["config"])
    e = run_cell.mix_file(w["traffic"])["engine"]
    B = e["max_batch"]
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    cfg = get_config(conf["program_config"])
    shapes = jax.eval_shape(lambda: (
        init_params(jax.random.PRNGKey(0), cfg),
        init_draft_params(jax.random.PRNGKey(0), cfg)))
    params, dparams = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
        shapes)
    eng = build_engine(cfg, params, dparams, engine="paged", max_batch=B,
                       max_len=e["max_len"], block_size=e["block_size"],
                       prefill_chunk=e["prefill_chunk"],
                       pool_frac=(e["pool_tokens"] + 0.5) / (B * e["max_len"]))
    t = time.time()
    compiled = eng.lower_step(B).compile()
    ma = compiled.memory_analysis()
    print(f"{w['name']}: max_batch={B} max_len={e['max_len']} "
          f"pool_tokens={e['pool_tokens']} compile_s={time.time() - t:.1f}")
    print(f"  argument_bytes={ma.argument_size_in_bytes} "
          f"output_bytes={ma.output_size_in_bytes} "
          f"temp_bytes={ma.temp_size_in_bytes} "
          f"alias_bytes={ma.alias_size_in_bytes}")
    print(f"  tpu_custom_call sites: "
          f"{compiled.as_text().count('tpu_custom_call')}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
