"""Compile a configuration's task program for a described v5e chip.

The third rehearsal of the ``on-chip-measurement`` guide, for sizing the
minibatch: no chip is attached, nothing runs, and what comes out is the
compiler's own ``memory_analysis`` of the fused task program
(``core/step.py::build_multi_step`` over ``steps`` minibatches), or its
refusal. The numbers it gave are written into the configuration files.

    JAX_PLATFORMS=cpu python -m benchmark.lib.rehearse gpt2-medium 8 16 32

A compile that passes is not a chip run.
"""

import json
import os
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")


def rehearse(config: str, minibatch: int, steps: int = 8):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import paths
    from elasticdl_tpu.core.model_spec import get_model_spec
    from elasticdl_tpu.core.step import build_multi_step, build_train_step
    from elasticdl_tpu.core.train_state import init_train_state

    # The model asks which backend it runs on and would trace its CPU
    # branch (dense attention) here; the rehearsal is of the TPU branch.
    jax.default_backend = lambda: "tpu"
    base = os.environ.get("REHEARSE_BASE", paths.BENCH)
    cfg = paths.load_json(os.path.join(base, "configs", f"{config}.json"))
    spec = get_model_spec(os.path.join(base, "models"), cfg["model_def"])
    seq = cfg["seq_len"]
    topo = topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    one = {"features": jnp.zeros((minibatch, seq), jnp.int32),
           "labels": jnp.zeros((minibatch, seq), jnp.int32),
           "mask": jnp.ones((minibatch,), jnp.float32)}
    state = jax.eval_shape(
        lambda: init_train_state(spec.model, spec.make_optimizer(), one))
    if steps > 1:
        batch = jax.tree.map(
            lambda x: jnp.zeros((steps,) + x.shape, x.dtype), one)
        program = build_multi_step(spec.loss)
    else:
        batch, program = one, build_train_step(spec.loss)
    started = time.monotonic()
    compiled = program.lower(on_chip(state), on_chip(batch)).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    return {
        "config": config, "minibatch": minibatch, "steps": steps,
        "remat": bool(cfg["remat"]),
        "compile_s": round(time.monotonic() - started, 1),
        "argument_bytes": mem.argument_size_in_bytes,
        "output_bytes": mem.output_size_in_bytes,
        "alias_bytes": mem.alias_size_in_bytes,
        "temp_bytes": mem.temp_size_in_bytes,
        "code_bytes": mem.generated_code_size_in_bytes,
        # Donated state is aliased: arguments + temporaries + what of
        # the output is not an alias.
        "total_bytes": (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes - mem.alias_size_in_bytes
                        + mem.generated_code_size_in_bytes),
        "pallas_custom_calls": text.count("tpu_custom_call"),
    }


def main(argv):
    config, sizes = argv[0], [int(x) for x in argv[1:]]
    steps = int(os.environ.get("REHEARSE_STEPS", "8"))
    for minibatch in sizes:
        try:
            print(json.dumps(rehearse(config, minibatch, steps)), flush=True)
        except Exception as exc:
            print(json.dumps({
                "config": config, "minibatch": minibatch, "steps": steps,
                "refused": f"{type(exc).__name__}: {str(exc)[:600]}"}),
                flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
