"""Compile the cells' programs at their real sizes for a DESCRIBED v5e chip
(no chip attached) and print what the compiler's memory analysis reads:

    JAX_PLATFORMS=cpu python -m benchmark.tools.aot_check

A compile that passes is not a chip run; nothing printed here is a device
metric. The train leg compiles the model's loss and gradients (the step's
temporaries) with the flash kernels pinned; the serve legs compile the
engine's own decode wave and prefill chunk with the paged kernel pinned.
"""

import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["ROCKET_TPU_PAGED_DECODE"] = "pallas"

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from benchmark.drivers import common  # noqa: E402
from benchmark.run import HERE, load_json  # noqa: E402


def main():
    jax.config.update("jax_enable_compilation_cache", False)
    from rocket_tpu.models.transformer import TransformerLM, next_token_loss
    from rocket_tpu.serve import ServeConfig
    from rocket_tpu.serve.engine import (
        DECODE_DONATE, PREFILL_DONATE, abstract_wave_inputs,
        build_decode_wave, build_prefill_step,
    )

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    place = lambda tree: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), tree
    )
    gib = lambda b: round(b / 2**30, 3)  # noqa: E731

    def report(name, compiled):
        m = compiled.memory_analysis()
        print(name, {
            "arguments_GiB": gib(m.argument_size_in_bytes),
            "outputs_GiB": gib(m.output_size_in_bytes),
            "temporaries_GiB": gib(m.temp_size_in_bytes),
            "aliased_GiB": gib(m.alias_size_in_bytes),
            "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
        }, flush=True)

    # -- gpt2-medium.train: loss + gradients at B=8 x T=1024 ---------------
    config = load_json(HERE / "configs" / "gpt2-medium.json")
    tcfg = dataclasses.replace(common.transformer_config(config), attention_impl="flash")
    model = TransformerLM(tcfg)
    objective = next_token_loss()
    params = place(jax.eval_shape(model.init, jax.random.key(0))["params"])
    tokens = place(jax.ShapeDtypeStruct((8, 1024), jnp.int32))

    def loss_and_grads(params, tokens):
        def loss(p):
            out, _ = model.apply({"params": p, "state": {}}, {"tokens": tokens}, mode="train")
            return objective(out).astype(jnp.float32)
        return jax.value_and_grad(loss)(params)

    report("gpt2-medium loss+grads B=8 T=1024",
           jax.jit(loss_and_grads).lower(params, tokens).compile())

    # -- gpt2-large.chat-busy: the engine's two programs ---------------------------
    config = load_json(HERE / "configs" / "gpt2-large.json")
    cell = load_json(HERE / "workloads" / "gpt2-large.chat-busy.json")["engine"]
    model = TransformerLM(common.transformer_config(config))
    sc = ServeConfig(max_slots=cell["max_slots"], block_len=cell["block_len"],
                     prefill_chunk=cell["prefill_chunk"],
                     decode_waves_per_dispatch=cell["decode_waves_per_dispatch"])
    spec, mb, _, waves = sc.resolve(model.config)
    decode_args, prefill_args = abstract_wave_inputs(
        model, spec, max_slots=sc.max_slots, max_blocks_per_seq=mb,
        prefill_chunk=sc.prefill_chunk,
    )
    print("pool_GiB", gib(spec.pool_bytes), "blocks", spec.num_blocks)
    report("gpt2-large decode wave", jax.jit(
        build_decode_wave(model, waves=waves), donate_argnums=DECODE_DONATE,
    ).lower(*place(decode_args)).compile())
    report("gpt2-large prefill chunk", jax.jit(
        build_prefill_step(model), donate_argnums=PREFILL_DONATE,
    ).lower(*place(prefill_args)).compile())


if __name__ == "__main__":
    main()
