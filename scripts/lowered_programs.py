#!/usr/bin/env python
"""What the serving engine's device programs lower to, as one line a program.

For the engines of the benchmark's serving cells (built from the cell and
configuration files under ``benchmarks/``) every jitted program the traffic
reaches is lowered for a DESCRIBED v5e chip from abstract operands (nothing
runs, no weights are made) and printed as::

    <cell> <program> operands=<leaves> donated=<leaf indices> sha256=<text>

The text is ``Lowered.as_text()``, which carries no source locations, so two
trees whose lines are equal hand the compiler the same modules with the same
donated arguments, and a compile cache filled by one serves the other.  A
Pallas kernel's body does carry its source file's path, so the two trees are
unpacked, one after the other, at ONE path.  A restructuring of the engine's
host side shows that it changed no program by::

    for rev in <parent> HEAD; do
      rm -rf /tmp/tree && mkdir /tmp/tree
      git archive $rev | tar -x -C /tmp/tree
      python scripts/lowered_programs.py --tree /tmp/tree > /tmp/$rev.txt
    done; diff /tmp/<parent>.txt /tmp/HEAD.txt

About a minute a tree at full depth (``--layers N`` lowers ``gpt2_xl`` at N
layers); ``--dump DIR`` keeps the texts for a ``diff`` when a line differs.
Each program is lowered with the operands the engine passes: a tree whose
programs take a trailing block table gets ``None`` there, as the fixed-slot
pool of both cells passes.
"""

import argparse
import dataclasses
import hashlib
import json
import os
import sys


def main() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tree", default=here, help="checkout to import from")
    ap.add_argument("--layers", type=int, default=0,
                    help="depth to lower gpt2_xl at (0: as configured)")
    ap.add_argument("--dump", default=None, help="directory for the texts")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    sys.path[:0] = [tree, os.path.join(tree, "benchmarks")]
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from drivers.serve_moe import model_config
    from lib import weights

    from tpu_parallel.models import GPTLM
    from tpu_parallel.serving import cache_pool, engine
    from tpu_parallel.train_lib import MODEL_REGISTRY

    assert os.path.abspath(engine.__file__).startswith(tree), engine.__file__
    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2"
    ).devices[0])
    jax.default_backend = lambda: "tpu"  # the kernels' branch, not interpret

    def spec(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    ints = lambda *shape: spec(jnp.int32, *shape)
    floats = lambda *shape: spec(jnp.float32, *shape)
    bools = lambda *shape: spec(jnp.bool_, *shape)
    key = spec(jnp.uint32, 2)
    on_chip = lambda x, dtype=None: spec(dtype or x.dtype, *x.shape)

    def read(*rel):
        with open(os.path.join(tree, "benchmarks", *rel)) as f:
            return json.load(f)

    def with_table(fn, *operands):
        try:  # the engine passes the block table last; None: fixed-slot pool
            return fn.lower(*operands, None)
        except TypeError:  # a tree whose fixed-slot programs take no table
            return fn.lower(*operands)

    def report(cell, name, lowered):
        text = lowered.as_text()
        leaves = jax.tree_util.tree_leaves(lowered.args_info)
        donated = [i for i, a in enumerate(leaves) if a.donated]
        digest = hashlib.sha256(text.encode()).hexdigest()
        print(f"{cell} {name} operands={len(leaves)} donated="
              f"{','.join(map(str, donated)) or '-'} sha256={digest}",
              flush=True)
        if args.dump:
            os.makedirs(args.dump, exist_ok=True)
            path = os.path.join(args.dump, f"{cell}.{name}.mlir")
            with open(path, "w") as f:
                f.write(text)

    def programs(cell_name, cfg, chunk, prefill_shapes):
        model = GPTLM(cfg)
        eng = read("workloads", f"{cell_name}.json")["engine"]
        n = eng["n_slots"]
        params = jax.tree.map(
            lambda x: on_chip(x, getattr(jnp, eng["served_parameters"])),
            jax.eval_shape(lambda: model.init(
                {"params": jax.random.PRNGKey(0)},
                jnp.zeros((1, 16), jnp.int32), train=False,
            ))["params"],
        )
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: cache_pool._pool_cache_shapes(model, p, n), params
        ))
        state = (ints(n), ints(n), ints(n), bools(n), ints(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        prefill, extend = engine._engine_fns(model)[:2]
        report(cell_name, "fused_8", with_table(
            engine._fused_engine_fn(model, 8), params, state, knobs, pool, key
        ))
        if chunk:
            chunk_ops = (ints(n, chunk), ints(n), ints(n), bools(n), ints(n))
            report(cell_name, f"unified_8x{chunk}", with_table(
                engine._unified_engine_fn(model, 8, chunk),
                params, state, knobs, chunk_ops, pool, key,
            ))
        for rows, width in prefill_shapes:
            lowered = prefill.lower(
                params, ints(rows, width), ints(rows, width), ints(rows), key
            )
            report(cell_name, f"prefill_{rows}x{width}", lowered)
            fresh = jax.tree.map(on_chip, lowered.out_info[1])
            report(cell_name, f"extend_{rows}x{width}", with_table(
                extend, params, ints(rows, width), ints(rows, width),
                ints(rows), ints(rows), fresh, key,
            ))

    def block_programs(cell):
        """The block-diffusion cell's two programs: the whole-prompt prefill
        at its first bucket and the tick of 8 block forwards."""
        from drivers.serve_blockgen import model_config as block_config

        eng = cell["engine"]
        cfg = block_config(read("configs", "sdar_30b_a3b_depth6.json"), eng)
        model, n, size = GPTLM(cfg), eng["n_slots"], cfg.block_len
        params = jax.tree.map(
            lambda x: on_chip(x, getattr(jnp, eng["served_parameters"])),
            jax.eval_shape(lambda: model.init(
                {"params": jax.random.PRNGKey(0)},
                jnp.zeros((1, 16), jnp.int32), train=False,
            ))["params"],
        )
        prefill, tick = engine._block_engine_fns(model, 8)
        width = eng["prefill_buckets"][0]
        report(cell["name"], f"block_prefill_1x{width}", prefill.lower(
            params, ints(1, width), ints(1, width)
        ))
        pool = jax.tree.map(on_chip, jax.eval_shape(
            lambda p: engine._block_prefill_core(
                model, p, jnp.zeros((n, 16), jnp.int32),
                jnp.zeros((n, 16), jnp.int32),
            )[0], params,
        ))
        state = (ints(n, size), bools(n, size), ints(n, size), ints(n),
                 ints(n), ints(n), bools(n), ints(n), ints(n, size), bools(n))
        knobs = (ints(n), floats(n), ints(n), floats(n))
        report(cell["name"], "block_tick_8", with_table(
            tick, params, state, knobs, pool, key
        ))

    xl_cell = read("workloads", "serve-gpt2_xl-batch.json")["engine"]
    xl_file = read("configs", "gpt2_xl.json")
    xl = MODEL_REGISTRY[xl_file["registry"]](**weights.model_overrides(
        xl_file, remat=False, **xl_cell.get("model_overrides", {})
    ))
    if args.layers:
        xl = dataclasses.replace(xl, n_layers=args.layers)
    programs("serve-gpt2_xl-batch", xl, xl_cell["prefill_chunk_tokens"],
             [(xl_cell["max_prefills_per_tick"], 128)])
    moe_cell = read("workloads", "serve-command_a_plus_share8-longshort.json")
    programs(
        moe_cell["name"],
        model_config(read("configs", "command_a_plus_share8.json"),
                     moe_cell["engine"]),
        0, [(1, moe_cell["engine"]["prefill_buckets"][0])],
    )
    from drivers.serve_hybrid import model_config as hybrid_config

    hybrid_cell = read("workloads", "serve-granite_4_0_h_micro-shortchat.json")
    programs(
        hybrid_cell["name"],
        hybrid_config(read("configs", "granite_4_0_h_micro.json"),
                      hybrid_cell["engine"]),
        0, [(1, hybrid_cell["engine"]["prefill_buckets"][0])],
    )
    block_programs(read("workloads", "serve-sdar_30b_a3b_depth6-blockgen.json"))
    # cells 6 and 7 (a tree that has no such driver is an older one: skipped)
    for driver, config_file, cell_file in (
        ("serve_latent_moe", "nemotron_3_super_120b_share4.json",
         "serve-nemotron_3_super_120b_share4-reasoning.json"),
        ("serve_latent_attn", "openpangu_ultra_moe_718b_share16.json",
         "serve-openpangu_ultra_moe_718b_share16-longdoc.json"),
    ):
        if not os.path.exists(
            os.path.join(tree, "benchmarks", "drivers", driver + ".py")
        ):
            continue
        import importlib

        family = importlib.import_module("drivers." + driver)
        cell = read("workloads", cell_file)
        programs(
            cell["name"], family.model_config(read("configs", config_file),
                                              cell["engine"]),
            0, [(1, cell["engine"]["prefill_buckets"][0])],
        )


if __name__ == "__main__":
    main()
