"""Compile a ``Trainer`` step at real size for a described TPU v5e:2x2.

    JAX_PLATFORMS=cpu python3 chipbench/rehearse_train.py --config granite_8b \\
        --layers 12 --remat full --batch 2 --seq 4096

No chip is needed: the TPU compiler compiles for four chips that are
described and not attached, on a (data=1, model=4) mesh, and refuses a
step that does not fit.  It prints the step's ``memory_analysis()`` per
device and counts the collectives in the compiled program.  Nothing runs,
so it says nothing about times or results.  This is the rehearsal for the
four-chip training cell that PERF.md lists as an open question.
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
    ap.add_argument("--config", required=True, help="a repro.configs name, e.g. granite_8b")
    ap.add_argument("--layers", type=int, required=True)
    ap.add_argument("--remat", required=True)
    ap.add_argument("--batch", type=int, required=True, help="sequences per step")
    ap.add_argument("--seq", type=int, required=True)
    args = ap.parse_args(argv)

    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import Model
    from repro.optim import AdamWConfig, adamw_init
    from repro.runtime import TrainConfig, Trainer
    from repro.sharding import default_rules, logical_to_sharding

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_host_mesh(model=4, devices=topo.devices)
    cfg = dataclasses.replace(get_config(args.config), n_layers=args.layers)

    # the Trainer's own step, built without placing anything on a device
    tr = Trainer.__new__(Trainer)
    tr.model_cfg, tr.opt_cfg = cfg, AdamWConfig()
    tr.cfg = TrainConfig(remat=args.remat)
    tr.mesh = mesh
    tr.model = Model(cfg, attn_impl="chunked", remat=args.remat)
    tr.rules = default_rules(mesh, n_experts=0, fsdp=False)
    tr._build()

    shapes, axes = tr.model.abstract_init()
    psh = logical_to_sharding(axes, mesh, tr.rules, like=shapes)

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                            tree, shardings)

    params = placed(shapes, psh)
    opt = placed(jax.eval_shape(adamw_init, shapes),
                 {"mu": psh, "nu": psh, "count": NamedSharding(mesh, P())})
    rows = NamedSharding(mesh, P(tr.rules.get("batch"), None))
    batch = {k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32, sharding=rows)
             for k in ("tokens", "targets")}
    compiled = jax.jit(tr._step_fn, donate_argnums=(0, 1)).lower(params, opt, batch).compile()

    m, gib = compiled.memory_analysis(), 2.0 ** 30
    held = (m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes
            - m.alias_size_in_bytes)
    print(f"{args.config} {args.layers} layers, remat {args.remat}, {args.batch} x {args.seq} "
          f"tokens: arguments {m.argument_size_in_bytes / gib:.3f} GiB, outputs "
          f"{m.output_size_in_bytes / gib:.3f}, temporaries {m.temp_size_in_bytes / gib:.3f}, "
          f"aliased {m.alias_size_in_bytes / gib:.3f}; held at once {held / gib:.3f} GiB "
          f"per device", flush=True)
    text = compiled.as_text()
    print({op: text.count(op) for op in
           ("all-reduce", "all-gather", "reduce-scatter", "collective-permute")}, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
