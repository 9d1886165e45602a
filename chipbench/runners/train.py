"""The runner named by ``"runner": "train"``: the fused train step of one
configuration, in the layout the cell asks for, over a pool of resident
batches.

It names no cell, configuration or metric: the configuration's own module
builds the net and the batches (``build``, ``make_batch``,
``flops_per_sample``), the cell's file gives the traffic (``batch``, ``pool``,
``queue_depth``, ``warmup_steps``, ``trace_steps``), and what it returns is
the run's record, which each metric's reader takes its number from.

It calls the program only through its public entry points: ``gluon`` (in the
configuration's module), ``amp.convert_block``,
``fuse.make_fused_train_step`` and ``executor_cache.ensure_compile_cache``.
"""
import glob
import math
import os
import shutil
import time

from chipbench import trace_reduce

now = time.perf_counter


def fold_seed(seed):
    """``--seed`` may be wider than 32 bits; the program's seed is an int32."""
    import numpy as onp
    return int(onp.random.SeedSequence(seed).generate_state(1)[0] >> 1)


def steady_loop(step, pool, first, depth, until=None, steps=None):
    """Dispatch step i, then wait for step i - depth: ``depth`` steps are
    always queued behind the one that runs, so a host that stalls for less
    than that many steps does not idle the device.  Stops dispatching at the
    host time ``until`` or after ``steps``, then drains.  Returns the losses
    (device scalars, not read back here), the host time each ``step(x, y)``
    call took to return, and the host time at which each step's loss was
    seen ready."""
    import jax
    losses, dispatch_s, done_at = [], [], []

    def wait_for_oldest():
        with jax.profiler.TraceAnnotation("bench.wait"):
            losses[len(done_at)].block_until_ready()
        done_at.append(now())

    while (len(losses) < steps) if steps is not None else (now() < until):
        x, y = pool[(first + len(losses)) % len(pool)]
        t = now()
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            losses.append(step(x, y))
        dispatch_s.append(now() - t)
        if len(losses) - len(done_at) > depth:
            wait_for_oldest()
    while len(done_at) < len(losses):
        wait_for_oldest()
    return losses, dispatch_s, done_at


def traced_steps(step, pool, first, depth, steps, out_dir, devices, say):
    """The same loop for ``steps`` steady steps under the profiler; the
    trace goes to ``out_dir`` (ignored by git) and is reduced from there."""
    import jax
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0     # host spans come from TraceAnnotation
    jax.profiler.start_trace(out_dir, profiler_options=options)
    try:
        losses, _, _ = steady_loop(step, pool, first, depth, steps=steps)
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        say(f"[trace] the profiler wrote no .xplane.pb under {out_dir}")
        return losses, None
    say(f"[trace] {steps} steps traced into {found[0]} "
        f"({os.path.getsize(found[0]) / 2**20:.1f} MiB)")
    reduced = trace_reduce.reduce_trace(
        trace_reduce.read_events(found[0]), steps=steps,
        device_ids=[d.id for d in devices])
    if reduced is None:
        say("[trace] no device plane with operations in the trace: every "
            "metric read from it is left out")
        return losses, None
    busy, window = reduced["busy_s"], reduced["window_s"]
    say(f"[trace] device busy {busy:.4f} s of a {window:.4f} s window over "
        f"{steps} steps ({1e3 * window / steps:.3f} ms a step)")
    for name, seconds in (reduced["breakdown"]["device_ops"]
                          + reduced["top_instructions"]):
        say(f"[trace]   {1e3 * seconds / steps:8.3f} ms a step "
            f"{100 * seconds / busy:5.1f} %  {name[:110]}")
    for name, seconds in reduced["breakdown"]["idle_gaps"][:5]:
        say(f"[trace]   idle gap {1e3 * seconds:8.3f} ms under {name}")
    return losses, reduced


def reference_check(job, checks):
    """The plain reference, outside every timed part: the configuration in
    float32 at the highest matmul precision, one step on a small batch, on the
    chip and on the host's CPU device in this same process.  The cell's file
    gives the batch, the keys of the configuration to override (the dtype; no
    dropout, so that the comparison does not lean on two backends drawing one
    mask) and the tolerance.  It holds the chip to the host, not the framework
    to an independent implementation."""
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    ref, model, seed = job["cell"]["reference"], job["model"], job["seed"]
    config = dict(job["config"], **ref["config"])
    mx.random.seed(fold_seed(seed))
    built = model.build(seed, config)
    batch = model.make_batch(seed, 0, ref["batch"], config,
                             job["cell"]["traffic"])
    losses = []
    for target in (jax.devices("cpu")[0], job["devices"][0]):
        with jax.default_matmul_precision("highest"):
            step = make_fused_train_step(
                built["net"], built["loss"], built["optimizer"],
                dict(built["optimizer_params"]))
            step.params, step.aux, step.opt_state, step._key = \
                jax.device_put((step.params, step.aux, step.opt_state,
                                step._key), target)
            loss = step(*jax.device_put(batch, target))
        checks[f"the reference step ran on {target}"] = \
            loss.devices() == {target}
        losses.append(float(loss))
    host, chip = losses
    checks[f"float32 one-step loss at batch {ref['batch']}: host cpu "
           f"{host:.6f}, {job['devices'][0].platform} at highest matmul "
           f"precision {chip:.6f}, relative difference "
           f"{abs(chip - host) / abs(host):.1e} within {ref['rel_tol']:g}"] = \
        abs(chip - host) <= ref["rel_tol"] * abs(host)


def device_memory_peak(device):
    """Peak bytes of the device's memory taken.  The TPU runtime counts the
    buffers the process holds (``peak_bytes_in_use``) apart from what it
    reserves for running programs' temporaries (``peak_bytes_reserved``: read
    8.1 GiB where the step's compiled program plans 8.1 GiB of temporaries);
    free memory is the limit less both, so the peak is their sum."""
    stats = device.memory_stats() or {}
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def check_losses(losses, pool, n_classes, checks):
    """Every loss finite; the first near that of uniform logits; the lowest
    of the last pass over the pool below the first."""
    checks["every loss finite"] = all(math.isfinite(v) for v in losses)
    checks[f"first loss {losses[0]:.3f} within 3.0 of ln({n_classes}) = "
           f"{math.log(n_classes):.3f}"] = \
        abs(losses[0] - math.log(n_classes)) < 3.0
    last = min(losses[-pool:])
    checks[f"lowest loss of the last pass over the pool {last:.3f} below "
           f"the first {losses[0]:.3f}"] = last < losses[0]


def run(job):
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import numpy as onp
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, executor_cache
    from incubator_mxnet_tpu.fuse import make_fused_train_step

    say, cell, config, model = (job["say"], job["cell"], job["config"],
                                job["model"])
    traffic, devices, seed = cell["traffic"], job["devices"], job["seed"]
    batch, pool_size = traffic["batch"], traffic["pool"]
    depth = traffic["queue_depth"]
    say(f"[setup] compile cache {executor_cache.ensure_compile_cache()}")

    # ---- set-up: all of it counts as set-up time
    mx.random.seed(fold_seed(seed))
    built = model.build(seed, config)
    net = built["net"]
    amp.convert_block(net, config["dtype"])     # the dtype it is served in
    if len(devices) > 1:
        mesh = Mesh(onp.array(devices), ("dp",))
        where, kwargs = NamedSharding(mesh, P("dp")), {"mesh": mesh}
    else:
        where, kwargs = devices[0], {}
    step = make_fused_train_step(net, built["loss"], built["optimizer"],
                                 dict(built["optimizer_params"]), **kwargs)
    pool = [jax.device_put(model.make_batch(seed, i, batch, config, traffic),
                           where)
            for i in range(pool_size)]
    t_built = now()
    say(f"[setup] net, step and a pool of {pool_size} batches of {batch} "
        f"built and placed {t_built - job['process_start']:.1f} s after "
        "process start")
    first_loss = step(*pool[0])
    first_loss.block_until_ready()
    compile_s = now() - t_built
    warm, _, _ = steady_loop(step, pool, 1, depth,
                             steps=traffic["warmup_steps"])
    say(f"[setup] first call (compile or cache read + one step) "
        f"{compile_s:.2f} s; {len(warm)} warm-up steps")
    compiles_before = step._executor.compile_count

    # ---- the measured window
    window_open = now()
    in_window, dispatch_s, done_at = steady_loop(
        step, pool, 1 + len(warm), depth, until=window_open + job["seconds"])
    say(f"[window] {len(in_window)} steps dispatched in {job['seconds']:g} s,"
        f" the last ready {done_at[-1] - window_open:.3f} s after it opened")

    # ---- after the window: the traced steps, read-backs and checks
    traced, trace = [], None
    if job["trace"]:
        traced, trace = traced_steps(
            step, pool, 1 + len(warm) + len(in_window), depth,
            traffic["trace_steps"], job["out_dir"], devices, say)
    checks = {}
    compiles = step._executor.compile_count
    checks[f"compile_count {compiles} == 1, none inside the window"] = \
        compiles == 1 and compiles_before == 1
    losses = [float(v) for v in jax.device_get(
        [first_loss] + warm + in_window + traced)]
    check_losses(losses, pool_size, model.n_classes(config), checks)
    failed = sum(1 for v in losses[1 + len(warm):1 + len(warm)
                                   + len(in_window)] if not math.isfinite(v))
    leaves = jax.tree_util.tree_leaves(
        (step.params, step.aux, step.opt_state))
    spans = {frozenset(leaf.devices()) for leaf in leaves}
    checks[f"all {len(leaves)} state leaves on the cell's "
           f"{len(devices)} device(s)"] = spans == {frozenset(devices)}
    peak = max(device_memory_peak(d) for d in devices)
    if job["trace"] and "reference" in cell:
        reference_check(job, checks)
    for what, ok in checks.items():
        say(f"[check] {'ok  ' if ok else 'FAIL'} {what}")
    say("[check] losses: first " + " ".join(f"{v:.3f}" for v in losses[:4])
        + "  last " + " ".join(f"{v:.3f}" for v in losses[-pool_size:]))
    return {
        "correct": all(checks.values()), "attempted": len(in_window),
        "failed": failed, "memory_peak_bytes": int(peak),
        "process_start": job["process_start"], "window_open": window_open,
        "step_done_at": done_at, "step_dispatch_s": dispatch_s,
        "first_call_s": compile_s, "samples_per_step": batch,
        "flops_per_sample": model.flops_per_sample(config, traffic),
        "chips": len(devices), "peaks": job["peaks"], "trace": trace,
        "cell": cell, "config": config,
    }
