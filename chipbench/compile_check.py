"""Third rehearsal: compile a cell's step programs at their real size for a
described ``v5e:2x2``, with no chip attached, and print what a chip would hold.

    JAX_PLATFORMS=cpu python chipbench/compile_check.py --config mistral-7b-zero3-fsdp4 [--layers 8]
    JAX_PLATFORMS=cpu python chipbench/compile_check.py --config mistral-7b-serve-16l

Nothing runs, so this says what fits and what the compiler refuses, never a
time.  It is a script to run by hand (it loads the TPU compiler at its top
level, which a test file must never do).  The engines build their state on
real devices, so the script hands them shapes instead: it builds the program's
own step function around ``jax.eval_shape`` state."""

import argparse
import inspect
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding  # noqa: E402

from chipbench import common  # noqa: E402
from deepspeed_tpu.ops import _pallas  # noqa: E402

# The program picks its kernels by jax.default_backend(), which is the CPU
# here.  The script, not the program, steers that: before any module binds it.
_pallas.use_pallas = lambda: True

GiB = float(1 << 30)


def report(tag, compiled):
    m = compiled.memory_analysis()
    total = m.argument_size_in_bytes + m.output_size_in_bytes + m.temp_size_in_bytes \
        - m.alias_size_in_bytes
    print(f"[{tag}] arguments={m.argument_size_in_bytes / GiB:.2f}GiB "
          f"outputs={m.output_size_in_bytes / GiB:.2f}GiB temp={m.temp_size_in_bytes / GiB:.2f}GiB "
          f"aliased={m.alias_size_in_bytes / GiB:.2f}GiB held={total / GiB:.2f}GiB a chip",
          flush=True)
    return total


def check_train(config, sizes, traffic, topo):
    """The ZeRO-3 step of ``Engine`` with its state as shapes on the described mesh."""
    from deepspeed_tpu.parallel import MeshTopology
    from deepspeed_tpu.runtime import engine as engine_mod
    ref = common.load_module("references", config["reference"])
    seq, micro = traffic["params"]["seq_len"], traffic["params"]["micro_batch_per_chip"]
    module, model_cfg = common.program_model(config, sizes, max_seq_len=seq)
    topology = MeshTopology.from_axis_dict(dict(config["mesh"]), devices=topo.devices)

    def shapes_not_arrays(self, param_init_fn):
        # Engine._init_state_sharded with the jitted constructor left unrun
        def make_state():
            p = param_init_fn()
            master = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
            return engine_mod.TrainState(step=jnp.zeros((), jnp.int32), params=master,
                                         opt_state=self._opt_init(master), loss_scale=None,
                                         rng=jax.random.PRNGKey(0))
        shapes = jax.eval_shape(make_state)
        shardings = self._state_shardings(shapes)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), shapes, shardings)

    engine_mod.Engine._init_state_sharded = shapes_not_arrays
    import deepspeed_tpu
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=module.make_loss_fn(model_cfg),
        model_parameters=jax.eval_shape(
            lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.float32)),
        param_init_fn=lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.float32),
        topology=topology,
        config=dict(config["engine"]["config"], train_micro_batch_size_per_gpu=micro))
    rows = engine.train_batch_size
    data = NamedSharding(topology.mesh, PartitionSpec(None, tuple(
        a for a in ("data", "fsdp") if topology.mesh.shape[a] > 1) or None))
    leaf = jax.ShapeDtypeStruct((1, rows, seq), jnp.int32, sharding=data)  # [gas, batch, seq]
    batch = {"input_ids": leaf, "labels": leaf}
    compiled = engine.train_step_fn.lower(engine.state, batch).compile()
    from deepspeed_tpu.ops._pallas import kernel_calls
    print(f"[train] layers={sizes['num_hidden_layers']} "
          f"params={common.count_params(engine.state.params) / 1e9:.3f}B "
          f"seq={seq} rows={rows} kernels={kernel_calls(compiled.as_text())}")
    return report("train step", compiled)


def check_serve(config, sizes, traffic_files, topo):
    """The ragged forward at the widest bucket each mix meets, and the decode
    burst, for one described chip."""
    from deepspeed_tpu.inference.v2.engine_v2 import InferenceEngineV2
    ref = common.load_module("references", config["reference"])
    module, model_cfg = common.program_model(config, sizes)
    one = SingleDeviceSharding(topo.devices[0])
    eng = config["engine"]
    bs, nb = eng["block_size"], eng["num_blocks"]

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), tree)

    params = on_chip(jax.eval_shape(
        lambda: ref.init_params(sizes, jax.random.PRNGKey(0), jnp.bfloat16)))
    kv = on_chip(jax.eval_shape(lambda: module.init_paged_cache(model_cfg, nb, bs,
                                                                dtype=jnp.bfloat16)))

    # what the engine hands its forward (engine_v2._build_fwd_jit): the step's
    # live-token bound, where the model's forward takes one
    kw = {}
    if "live_token_bound" in inspect.signature(module.forward_paged).parameters:
        kw["live_token_bound"] = eng.get("token_budget", inspect.signature(
            InferenceEngineV2.__init__).parameters["token_budget"].default)

    def fwd(params, kv, tokens, n_tokens, start_pos, tables):
        return module.forward_paged(model_cfg, params, tokens, n_tokens, start_pos, tables, kv,
                                    block_size=bs, **kw)

    worst = 0
    for name, traffic in traffic_files.items():
        p = traffic["params"]
        longest = p["prompt_lengths"]["max"] + p["max_new_tokens"]
        step = InferenceEngineV2.TABLE_STEP
        width = -(-(-(-longest // bs)) // step) * step
        n = min(32, 1 << (p["requests_per_wave"] - 1).bit_length())
        for t in (256, 1):
            args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
                    for s in ((n, t), (n,), (n,), (n, width))]
            compiled = jax.jit(fwd, donate_argnums=(1,)).lower(params, kv, *args).compile()
            worst = max(worst, report(f"serve {name} fwd n={n} t={t} b={width} {kw}", compiled))
    return worst


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", required=True)
    ap.add_argument("--layers", type=int)
    args = ap.parse_args()
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    config = common.load_json("configs", args.config + ".json")
    sizes = common.published_sizes(config, False)
    if args.layers:
        sizes["num_hidden_layers"] = args.layers
    cells = [w for w in bench["workloads"] if w["config"] == args.config]
    traffic = {w["traffic"]: common.load_json("traffic", w["traffic"] + ".json") for w in cells}
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    if config["entry"] == "train":
        held = check_train(config, sizes, next(iter(traffic.values())), topo)
    else:
        held = check_serve(config, sizes, traffic, topo)
    print(f"[compile_check] config={args.config} layers={sizes['num_hidden_layers']} "
          f"most_held={held / GiB:.2f}GiB of 15.75 usable")


if __name__ == "__main__":
    main()
