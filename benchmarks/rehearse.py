#!/usr/bin/env python3
"""Chipless rehearsal: compile a training cell's step at its real size for
a described `v5e:2x2` and print XLA's `memory_analysis()`, so the depth,
`remat` and batch written in a configuration are reproducible without
chip time.  Nothing runs; a compile that passes is not a chip run.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py --config opt-1.3b-1chip \
        [--chips 4] [--layers N] [--batch N] [--remat dots|full|none]

The program places its own parameters on real devices, so this script
hands `ParallelTrainer` the described devices and shapes in their place
(`on-chip-measurement` section 2): it traces the net symbolically, fills
the trainer's state with `ShapeDtypeStruct`s at the shardings the trainer
itself chooses, and lowers the trainer's own `_step_fn`.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time


def step_memory(cfg, chips, topo=None):
    """`memory_analysis()` of the compiled step of *cfg* over *chips*
    described v5e devices (of *topo*, else a `v5e:2x2` described here), as
    a dict of bytes per device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmarks.models import common as models_common

    topo = topo or topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
    devices = list(topo.devices)[:chips]
    family = importlib.import_module("benchmarks.models." + cfg["family"])
    train = cfg["train"]
    net, loss = family.build(cfg)
    trainer = models_common.make_trainer(net, loss, train, devices)
    rows = train["per_chip_batch"] * chips
    (xs, xd), (ys, yd) = family.sample_shapes(cfg, rows)
    trainer._trace(None, None)
    trainer._resolve_opt()
    trainer._frozen = frozenset()

    # parameter shapes: the reference's table, in the program's order
    table = family.reference.param_table(cfg)
    trained = [p for p in net.collect_params().values()
               if not p.name.endswith(("running_mean", "running_var"))]
    shapes = {p.name: shape for p, (shape, _) in zip(trained, table.values())}
    for aux in trainer.aux_names:
        for suffix in ("running_mean", "running_var"):
            if aux.endswith(suffix):
                shapes[aux] = shapes[aux[:-len(suffix)] + "gamma"]
    mp = trainer.multi_precision

    def sds(shape, dtype, name=None):
        probe = jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(
                trainer.mesh, trainer._spec_for(probe, name)))

    trainer._params = {n: sds(shapes[n], jnp.bfloat16 if mp else jnp.float32,
                              n) for n in trainer.param_names}
    slots = trainer._opt_n_states + (1 if mp else 0)
    trainer._opt_state = {n: tuple(sds(shapes[n], jnp.float32, n)
                                   for _ in range(slots))
                          for n in trainer.param_names}
    repl = NamedSharding(trainer.mesh, P())
    trainer._aux = {n: jax.ShapeDtypeStruct(shapes[n], jnp.float32,
                                            sharding=repl)
                    for n in trainer.aux_names}
    trainer._build_step()
    batch = NamedSharding(trainer.mesh, P("dp"))
    x = jax.ShapeDtypeStruct(
        xs, jnp.bfloat16 if mp and np.issubdtype(xd, np.floating) else xd,
        sharding=batch)
    y = jax.ShapeDtypeStruct(ys, yd, sharding=batch)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
    t0 = time.time()
    compiled = trainer._step_fn.lower(
        trainer._params, trainer._opt_state, trainer._aux, x, y, key,
        jax.ShapeDtypeStruct((), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    out = {k: int(getattr(mem, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "alias_size_in_bytes", "temp_size_in_bytes",
        "generated_code_size_in_bytes")}
    out["mosaic_calls"] = text.count('custom_call_target="tpu_custom_call"')
    out["all_gathers"] = text.count(" all-gather(") \
        + text.count(" all-gather-start(")
    out["all_reduces"] = text.count(" all-reduce(") \
        + text.count(" all-reduce-start(")
    out["reduce_scatters"] = text.count(" reduce-scatter(")
    out["compile_seconds"] = round(time.time() - t0, 1)
    return out


def main(argv=None):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--layers", type=int)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--remat")
    args = ap.parse_args(argv)
    with open(os.path.join(root, "benchmarks", "configs",
                           args.config + ".json")) as f:
        cfg = json.load(f)
    if args.layers:
        cfg["num_hidden_layers"] = args.layers
    if args.batch:
        cfg["train"]["per_chip_batch"] = args.batch
    if args.remat:
        cfg["train"]["remat"] = None if args.remat == "none" else args.remat
    import jax
    # a chipless compile cannot be read back from the persistent cache
    jax.config.update("jax_enable_compilation_cache", False)
    out = step_memory(cfg, args.chips)
    print(json.dumps({"config": args.config, "chips": args.chips,
                      "layers": cfg.get("num_hidden_layers"),
                      "per_chip_batch": cfg["train"]["per_chip_batch"],
                      "remat": cfg["train"].get("remat"), **out}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
