"""Compile a serving cell's programs at real size for a described TPU v5e.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse.py --workload stablelm_3b.decode_heavy

No chip is needed: the TPU compiler compiles for a chip that is described
and not attached, and refuses a program that does not fit.  For the cell's
``Server`` this compiles the decode step, the slot insert and a prefill at
each prompt length of the mix, and prints each program's
``memory_analysis()`` and the bytes it holds at once (arguments plus
outputs plus temporaries, less what donation aliases).  Nothing runs, so it
says nothing about times or results.
"""

import os
import pathlib
import sys

_ROOT = pathlib.Path(__file__).resolve().parents[1]
for _p in (str(_ROOT / "src"), str(_ROOT)):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main(argv=None) -> int:
    import argparse

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--topology", default="v5e:2x2")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.bench import Cell
    from chipbench.kinds.serve import program_config
    from repro.models import Model
    from repro.runtime.serving import ServeConfig, Server

    jax.config.update("jax_enable_compilation_cache", False)
    cell = Cell.load(args.workload)
    topo = topologies.get_topology_desc(platform="tpu", topology_name=args.topology)
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = program_config(cell.config)
    opts = cell.config["serve"]
    slots, max_len = int(opts["slots"]), int(opts["max_len"])

    def on_chip(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(Model(cfg).abstract_init()[0])
    server = Server(cfg, ServeConfig(batch_slots=slots, max_len=max_len), params)
    one = on_chip(jax.eval_shape(server._prefill_fn, params,
                                 jax.ShapeDtypeStruct((1, 8), jnp.int32))[1])
    state = on_chip(Server._tree_map_batch(
        lambda x, ax: jax.ShapeDtypeStruct(x.shape[:ax] + (slots,) + x.shape[ax + 1:], x.dtype),
        one))
    programs = {
        "decode": (server._decode, (params, jax.ShapeDtypeStruct((slots, 1), jnp.int32,
                                                                 sharding=chip), state)),
        "insert": (server._insert, (state, one, jax.ShapeDtypeStruct((), jnp.int32,
                                                                     sharding=chip))),
    }
    for n in sorted(cell.traffic["prompt_lengths"]):
        programs[f"prefill {n}"] = (server._prefill, (
            params, jax.ShapeDtypeStruct((1, n), jnp.int32, sharding=chip)))

    gib = 2.0 ** 30
    for name, (fn, shapes) in programs.items():
        mem = fn.lower(*shapes).compile().memory_analysis()
        held = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
        print(f"{args.workload} {name}: arguments {mem.argument_size_in_bytes / gib:.3f} GiB, "
              f"outputs {mem.output_size_in_bytes / gib:.3f}, temporaries "
              f"{mem.temp_size_in_bytes / gib:.3f}, aliased {mem.alias_size_in_bytes / gib:.3f}; "
              f"held at once {held / gib:.3f} GiB", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
