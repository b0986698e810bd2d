"""Compile a configuration's served executables for a described v5e chip.

    JAX_PLATFORMS=cpu python3 bench/compile_v5e.py gptneox-1b

Nothing runs and no chip is needed: the TPU compiler builds, for one
chip of a described ``v5e:2x2`` host, the four executables a cell's
window dispatches (``prefill_chunk``, ``_admit_update``, ``clear_slot``
and the fused ``loop`` at K = decode_block) at the configuration's batch
and max_seq, from shapes alone.  Prints each one's argument, output and
temporary bytes; the compiler raises where one does not fit.
"""

import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main() -> None:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from harness import cell
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import ServeEngine

    jax.config.update("jax_enable_compilation_cache", False)
    cfg = cell.load_json(os.path.join(BENCH, "configs",
                                      f"{sys.argv[1]}.json"))
    eng = cfg["engine"]
    b, s, k = eng["batch"], eng["max_seq"], eng["decode_block"]
    model = build_model(get_config(cfg["program"]["arch"]))
    one = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    on = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
        tree)
    shaped = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)
    # the engine's own methods, on an engine that holds no arrays
    engine = object.__new__(ServeEngine)
    engine.model, engine.batch, engine.max_seq = model, b, s
    engine._temperature, engine._top_k = 0.0, 0
    engine.spec, engine.mesh, engine._sh = None, None, None
    params = on(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    cache = on(jax.eval_shape(lambda: model.init_cache(b, s)))
    state = on(jax.eval_shape(engine._init_state))
    key = on(jax.eval_shape(lambda: jax.random.PRNGKey(0)))
    i32 = shaped((), jnp.int32)
    vocab = model.cfg.vocab_size
    jobs = {
        "prefill_chunk": (model.prefill_chunk, (
            params, cache, shaped((eng["prefill_chunk"],), jnp.int32),
            i32, i32, i32)),
        "_admit_update": (engine._admit_update, (
            state, shaped((1, vocab), jnp.float32), i32, i32, i32, i32,
            key)),
        "clear_slot": (model.clear_slot, (cache, i32)),
        f"loop[K={k}]": (engine._make_decode_loop(k),
                         (params, cache, state, key)),
    }
    for name, (fn, args) in jobs.items():
        fn = fn if hasattr(fn, "lower") else jax.jit(fn)
        ma = fn.lower(*args).compile().memory_analysis()
        print(f"{sys.argv[1]} batch {b} max_seq {s} {name}: arguments "
              f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs "
              f"{ma.output_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{ma.temp_size_in_bytes / 1e9:.3f} GB", flush=True)


if __name__ == "__main__":
    main()
