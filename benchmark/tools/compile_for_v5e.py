#!/usr/bin/env python3
"""Compile a cell's train program for a *described* v5e, without a chip.

A rehearsal, never a measurement: nothing runs, so this gives no time, rate
or utilization.  It answers two questions before chip time is spent:

- how many bytes one device needs for the program ``Trainer`` dispatches in
  device data mode (``make_device_chunk_runner``, one whole epoch per
  dispatch) at a batch size — ``memory_analysis()`` of one program, not what
  else the process keeps on the device;
- which collectives the compiler put into the ``data=N`` program.

Usage (``JAX_PLATFORMS=cpu`` stays set; libtpu compiles for ``v5e:2x2``)::

    python benchmark/tools/compile_for_v5e.py --model resnet18 \
        --batch-size 2048 --chips 1 [--examples 45000] [--patch-size 2]

Prints one JSON line.  The program's own builders are called with the
described devices and with shapes only (``jax.eval_shape``), as the
``on-chip-measurement`` guide's section 2 sets out.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18")
    ap.add_argument("--batch-size", type=int, default=256)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--examples", type=int, default=45000)
    ap.add_argument("--patch-size", type=int, default=0)
    ap.add_argument("--steps", type=int, default=0,
                    help="steps per dispatch (0 = the whole epoch)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_training_comparison_tpu.models import get_model
    from distributed_training_comparison_tpu.parallel import (
        make_mesh,
        state_shardings,
    )
    from distributed_training_comparison_tpu.train.optim import (
        configure_optimizers,
    )
    from distributed_training_comparison_tpu.train.state import (
        create_train_state,
    )
    from distributed_training_comparison_tpu.train.step import (
        make_device_chunk_runner,
    )

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = make_mesh(args.chips, devices=list(topo.devices))

    kw = dict(dtype=jnp.bfloat16, norm_dtype=jnp.float32)
    if args.model.startswith("vit"):
        kw.update(image_size=32, scan_unroll=-1)  # the Trainer's TPU choice
        if args.patch_size:
            kw["patch"] = args.patch_size
    model = get_model(args.model, **kw)
    steps = args.examples // args.batch_size
    hp = argparse.Namespace(
        lr=0.1, lr_decay_step_size=25, lr_decay_gamma=0.1, weight_decay=1e-4
    )
    tx, _ = configure_optimizers(hp, steps)
    state = jax.eval_shape(
        lambda k: create_train_state(model, k, tx), jax.random.key(0)
    )
    state_sh = state_shardings(mesh, state)
    repl = NamedSharding(mesh, P())

    def shaped(tree, shardings):
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            tree, shardings,
        )

    take = args.steps or steps
    runner = make_device_chunk_runner(
        mesh, args.batch_size, take, precision="bf16",
        state_sharding=state_sh, donate=False,
    )
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=repl)  # noqa: E731
    key = jax.eval_shape(lambda: jax.random.key(0))
    compiled = runner.lower(
        shaped(state, state_sh),
        sds((args.examples, 32, 32, 3), jnp.uint8),
        sds((args.examples,), jnp.int32),
        jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=repl),
        sds((), jnp.int32),
        sds((), jnp.int32),
    ).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    ops = collections.Counter(
        m.group(1)
        for m in re.finditer(
            r"=\s*\S+\s+(" + "|".join(COLLECTIVES) + r")(?:-start)?\(", text
        )
    )
    cost = compiled.cost_analysis() or {}
    if isinstance(cost, list):
        cost = cost[0]
    print(json.dumps({
        "compiled_for": "described v5e:2x2 (no chip; not a measurement)",
        "model": args.model, "batch_size": args.batch_size,
        "chips": args.chips, "steps_in_program": take,
        "bytes_per_device": {
            "arguments": mem.argument_size_in_bytes,
            "outputs": mem.output_size_in_bytes,
            "temporaries": mem.temp_size_in_bytes,
            "total_gib": round(
                (mem.argument_size_in_bytes + mem.output_size_in_bytes
                 + mem.temp_size_in_bytes - mem.alias_size_in_bytes) / 2**30, 3
            ),
        },
        "hlo_flops": cost.get("flops"),
        "collectives": dict(ops),
        "tpu_custom_calls": text.count("tpu_custom_call"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
