"""Entry point ``train``: ``deepspeed_tpu.initialize(...)`` and
``engine.train_batch()`` on the mesh the configuration file names, a fresh
seeded batch every step, each step ended by ``block_until_ready`` on its loss.

The first step's loss and gradient norm are the program's at the initial
weights; after the window the engine is dropped and the plain float32
reference computes both on the same weights and the same first batch."""

import contextlib
import gc
import os
import time

import numpy as np

from chipbench import common
from chipbench.common import say
from chipbench.reduce import shapes

HOST_ANNOTATIONS = ("batch_prep", "train_step", "chipbench.step")
WARMUP_STEPS = 2   # the first compiles; the second proves the program is reused
TRACED_STEPS = 5


def draw_params(ref, sizes, key, mesh):
    """float32 weights from ``key``, every matrix split over all the mesh's
    devices on its last axis."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(a for a in mesh.axis_names if mesh.shape[a] > 1)

    def placed(shape):
        last = len(shape.shape) - 1
        split = axes and last >= 1 and shape.shape[last] % mesh.size == 0
        return NamedSharding(mesh, P(*([None] * last + [axes])) if split else P())

    def init(key):
        return ref.init_params(sizes, key, jnp.float32)

    return jax.jit(init, out_shardings=jax.tree_util.tree_map(
        placed, jax.eval_shape(init, key)))(key)


def reference_check(ref, sizes, key, ids, devices, update_signs, control_format=None):
    """The reference on the weights the seed gives and one batch: its loss, its
    gradient's norm, and how much of its gradient's mass the first update
    (``update_signs``) does not descend.  With ``control_format`` the
    control (the reference with both operands of every projection rounded to that format) stands
    in the program's place: its loss, its norm, its descent step's signs.

    The plain code is the reference's; only where its arrays live is decided
    here (``draw_params``): every matrix split over the chips on its last axis,
    the batch on its first."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("chips",))
    params = draw_params(ref, sizes, key, mesh)
    batch = jax.device_put(ids, NamedSharding(mesh, P("chips" if len(ids) % len(devices) == 0
                                                      else None)))
    got = None
    if control_format is not None:
        def control(p, x):
            loss, grads = ref.loss_and_grads(sizes, p, x, matmul_format=control_format)
            signs = jax.tree_util.tree_map(lambda g: -jnp.sign(g).astype(jnp.int8), grads)
            return loss, ref.global_norm(grads), signs

        loss, norm, update_signs = jax.jit(control)(params, batch)
        got = (float(loss), float(norm))

    def check(p, x, signs):
        loss, grads = ref.loss_and_grads(sizes, p, x)
        return loss, ref.global_norm(grads), ref.not_descended_share(grads, signs)

    loss, norm, wrong = jax.jit(check)(params, batch, update_signs)
    return (float(loss), float(norm), float(wrong)), got


def first_update_signs(ref, sizes, key, params):
    """Sign of (params - the weights the seed gives), element by element, int8,
    placed as ``params`` are: the seed's weights are drawn again in shards and
    never held whole."""
    import jax
    import jax.numpy as jnp
    placement = jax.tree_util.tree_map(lambda x: x.sharding, params)

    def signs(p, key):
        old = jax.lax.with_sharding_constraint(ref.init_params(sizes, key, jnp.float32), placement)
        return jax.tree_util.tree_map(lambda a, b: jnp.sign(a - b).astype(jnp.int8), p, old)

    return jax.jit(signs, out_shardings=placement)(params, key)


def run(ctx):
    import jax
    import jax.numpy as jnp
    import deepspeed_tpu
    from deepspeed_tpu.parallel import MeshTopology

    config, sizes, args = ctx.config, ctx.sizes, ctx.args
    ref = common.load_module("references", config["reference"])
    traffic_params = dict(ctx.traffic["params"])
    if args.rehearse:
        traffic_params["seq_len"] //= config["rehearsal"]["traffic_scale"]["length_divisor"]
    traffic = common.load_module("generators", ctx.traffic["generator"]).Traffic(
        traffic_params, args.seed, sizes["vocab_size"])
    seq = traffic.seq_len
    module, model_cfg = common.program_model(config, sizes, max_seq_len=seq)

    t = time.perf_counter()
    topology = MeshTopology.from_axis_dict(dict(config["mesh"]), devices=ctx.devices)
    engine_config = dict(config["engine"]["config"],
                         train_micro_batch_size_per_gpu=traffic.micro_batch_per_chip)
    # The weights are drawn here, split over the mesh on each matrix's last axis,
    # and handed over as arrays; the engine places them by its own plan.  (Its
    # param_init_fn would close over the seed, and every seed would compile.)
    key = jax.random.PRNGKey(args.seed)
    params = draw_params(ref, sizes, key, topology.mesh)
    engine, _, _, _ = deepspeed_tpu.initialize(
        loss_fn=module.make_loss_fn(model_cfg), model_parameters=params,
        topology=topology, config=engine_config)
    jax.block_until_ready(engine.state)
    del params
    batch_rows = engine.train_batch_size
    tokens_per_step = batch_rows * seq
    say("train", params=f"{shapes.num_params(sizes) / 1e9:.3f}B", layers=sizes["num_hidden_layers"],
        mesh=dict(topology.mesh.shape), global_batch=batch_rows, seq=seq,
        tokens_per_step=tokens_per_step, init_s=f"{time.perf_counter() - t:.1f}")

    losses, norms = [], []

    def step(index):
        metrics = engine.train_batch(traffic.batch(index, batch_rows))
        losses.append(float(jax.block_until_ready(metrics.loss)))
        norms.append(metrics.grad_norm)

    t = time.perf_counter()
    step(0)
    # AdamW's first step moves every element against its gradient's sign, so
    # the weights after it tell which way the program's gradient pointed
    update_signs = first_update_signs(ref, sizes, key, engine.state.params)
    for i in range(1, WARMUP_STEPS):
        step(i)
    say("train", warmup_s=f"{time.perf_counter() - t:.1f}",
        warmup_losses=[round(x, 4) for x in losses])

    trace_dir = os.path.join(common.OUT, "trace")
    window = common.profiler_window(trace_dir) if args.trace else contextlib.nullcontext()
    done = 0
    with window:  # the profiler starts before the window does
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        while True:
            with jax.profiler.TraceAnnotation("chipbench.step") if args.trace \
                    else contextlib.nullcontext():
                step(WARMUP_STEPS + done)
            done += 1
            if (done >= TRACED_STEPS) if args.trace else (time.perf_counter() - t0 >= args.seconds):
                break
        window_s = time.perf_counter() - t0
    peak = common.memory_peak_bytes(ctx.devices)

    run = common.new_run(
        kind="train", setup_s=setup_s, window_s=window_s, steps=done,
        tokens=done * tokens_per_step, tokens_per_step=tokens_per_step, seq=seq,
        global_batch=batch_rows, sizes=sizes, peaks=ctx.peaks, chips=len(ctx.devices),
        memory_peak_bytes=peak, attempted=done, failed=0, trace=None, losses=losses)
    say("window", seconds=f"{window_s:.3f}", steps=done, tokens=run.tokens,
        step_ms=f"{1e3 * window_s / done:.1f}", loss_first=round(losses[0], 4),
        loss_last=round(losses[-1], 4))
    if args.trace:
        run.trace = common.reduce_trace(trace_dir, HOST_ANNOTATIONS, args.rehearse)

    # ---- correct, outside the window.  Every batch is fresh, so one step's
    # loss against another's is mostly the batches' difference: the first batch
    # is given once more, and its loss has to be below what it was at the start
    step(0)
    finite = bool(np.isfinite(losses).all())
    falls = losses[-1] < losses[0]
    say("correct", losses_finite=finite, loss_falls=falls, first_batch_at_start=round(losses[0], 5),
        first_batch_again=round(losses[-1], 5), steps_between=len(losses) - 1)
    # the engine makes room for the reference
    norms = [float(n) for n in norms]
    del engine
    gc.collect()
    limits = common.correct_limits(config, args.rehearse)
    ids = traffic.ids(0, batch_rows)
    control = limits["control"] if args.control else None
    if control:
        say("control", name=control["name"], what=control["what"])
    (want_loss, want_norm, wrong_way), stand_in = reference_check(
        ref, sizes, key, ids, ctx.devices, update_signs,
        control["format"] if control else None)
    got_loss, got_norm = stand_in or (losses[0], norms[0])
    loss_rel = abs(got_loss - want_loss) / abs(want_loss)
    norm_rel = abs(got_norm - want_norm) / abs(want_norm)
    say("correct", compared="share of the reference gradient's mass the first update does not descend",
        not_descended_share=f"{wrong_way:.6f}", limit=limits["not_descended_share_limit"])
    say("correct", compared="loss at the initial weights vs float32 reference",
        got=f"{got_loss:.6f}", want=f"{want_loss:.6f}", loss_rel_err=f"{loss_rel:.6f}",
        limit=limits["loss_rel_limit"])
    say("correct", compared="global gradient norm at the initial weights vs float32 reference",
        got=f"{got_norm:.6f}", want=f"{want_norm:.6f}", grad_norm_rel_err=f"{norm_rel:.6f}",
        limit=limits["grad_norm_rel_limit"])
    run.correct = bool(finite and falls and loss_rel <= limits["loss_rel_limit"]
                       and norm_rel <= limits["grad_norm_rel_limit"]
                       and wrong_way <= limits["not_descended_share_limit"])
    return run
