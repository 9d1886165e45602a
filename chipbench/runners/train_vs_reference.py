"""The runner named by ``"runner": "train_vs_reference"``: ``train.py``'s
set-up, window and record, with its loop and checks imported and nothing of
it edited, for a configuration that brings a plain reference
(``<config>.reference``: ``loss_and_grads(params, tokens, labels, config,
forced=, margin=, round_to=)``) and routed layers.

After the window it reads the routed layers' counters from the step's aux
state (no read-back inside a timed step): no step may have needed a second
pass over the held experts' buffer.  On the traced run it then makes the
comparison that decides ``correct``, at the timed sizes and the published
widths: the timed program's first loss, and through the same net (its
initial weights, the same batch, the same dtype policy) the logits of both
heads and the gradient of every parameter, against the reference in
float32 at the highest matmul precision on the same chip.  The limits are
the cell's (``reference`` in its file, each with its reason).
"""
import gc
import math

from chipbench.runners.train import (device_memory_peak, fold_seed, now,
                                     steady_loop, traced_steps)


def check_losses(losses, pool, expected_first, checks):
    """Every loss finite; the first near what uniform logits give both
    heads; every batch of the pool lower at its last step than at its first
    (step ``i`` takes batch ``i % pool``: a batch is held to itself, so the
    batches' different levels do not hide a small fall)."""
    checks["every loss finite"] = all(math.isfinite(v) for v in losses)
    checks[f"first loss {losses[0]:.3f} within 0.5 of the uniform "
           f"heads' {expected_first:.3f}"] = \
        abs(losses[0] - expected_first) < 0.5
    falls = [losses[b] - losses[b + (len(losses) - 1 - b) // pool * pool]
             for b in range(pool)]
    checks[f"every batch of the pool reads lower at its last step than at "
           f"its first: falls of {min(falls):.4f} to {max(falls):.4f}"] = \
        min(falls) > 0


def rel_l2(a, b):
    """``|a - b| / |b|`` over whole tensors, in float32."""
    import jax.numpy as jnp
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm((a - b).ravel())
                 / (jnp.linalg.norm(b.ravel()) + 1e-30))


def errors_against(reference, loss, main, mtp, grads):
    """The four numbers the limits bound: relative error of the loss, the
    larger relative L2 error of the two heads' logits, the relative L2
    error of all gradients taken as one vector, and the largest relative
    L2 error of a single parameter's gradient (with its name)."""
    import jax.numpy as jnp
    (ref_loss, (ref_main, ref_mtp, _)), ref_grads = reference
    off, size = {}, {}          # squared: |g - ref|^2 and |ref|^2, by name
    for name, ref_grad in ref_grads.items():
        size[name] = float(jnp.sum(jnp.square(ref_grad)))
        if name in grads and size[name] > 0.0:
            off[name] = float(jnp.sum(jnp.square(
                grads[name].astype(jnp.float32) - ref_grad)))
    worst = max(off, key=lambda n: off[n] / size[n])
    return {"loss": abs(float(loss) - float(ref_loss)) / abs(float(ref_loss)),
            "logits": max(rel_l2(main, ref_main), rel_l2(mtp, ref_mtp)),
            "grads": math.sqrt(sum(off.values())
                               / sum(size[n] for n in off)),
            "grad_worst": math.sqrt(off[worst] / size[worst]),
            "grad_worst_name": worst, "n_grads": len(off)}


def reference_comparison(job, built, batch, first_loss, checks, say):
    """See the module's docstring.  Runs after the step's state is freed."""
    import jax
    import jax.numpy as jnp
    from incubator_mxnet_tpu.gluon.nn.transformer_layers import \
        record_routing
    from incubator_mxnet_tpu.ndarray import NDArray
    limits, config = job["cell"]["reference"], job["config"]
    reference, device = job["model"].reference, job["devices"][0]
    net, loss_block = built["net"], built["loss"]
    params, apply = net.functional()
    routed = [n[:-len(".router_weight")] for n in params
              if n.endswith(".router_weight")]
    tokens, labels = batch

    def system(p, x, y):
        def loss_of(p):
            with record_routing() as chosen:
                outs = apply(p, x, training=True)
            loss = loss_block(*map(NDArray, outs), NDArray(y))
            return jnp.mean(loss.data), (outs, list(chosen))
        return jax.value_and_grad(loss_of, has_aux=True)(p)

    t = now()
    on_chip = jax.device_put(params, device)
    (loss, ((main, mtp), chosen)), grads = jax.jit(system)(
        on_chip, tokens, labels)
    forced = dict(zip(routed, chosen))
    jax.block_until_ready(grads)
    say(f"[reference] the net's own loss, logits and {len(grads)} gradients "
        f"at its initial weights in {now() - t:.1f} s")
    p32 = jax.jit(lambda p: {n: v.astype(jnp.float32)
                             for n, v in p.items()})(on_chip)
    del on_chip

    def run_reference(round_to=None):
        fn = jax.jit(lambda p, x, y, forced: reference.loss_and_grads(
            p, x, y, config, forced=forced, margin=limits["near_tie_margin"],
            round_to=round_to))
        out = fn(p32, tokens, labels, forced)
        jax.block_until_ready(out)
        return out

    t = now()
    ref = run_reference()
    say(f"[reference] float32 reference at the highest matmul precision on "
        f"{device} in {now() - t:.1f} s")
    checks[f"the reference ran on {device}"] = \
        ref[0][0].devices() == {device}

    # the routing: near ties take the program's choice; anything else that
    # differs is the program's error and shows in the numbers below
    routing = ref[0][1][2]
    near = float(jnp.mean(jnp.stack(
        [jnp.mean(r["near_tie"].astype(jnp.float32))
         for r in routing.values()])))
    differ = [float(jnp.mean(jnp.any(
        jnp.sort(r["own_idx"], -1) != jnp.sort(forced[n], -1), -1)
        & ~r["near_tie"])) for n, r in routing.items()]
    checks[f"share of tokens whose {config['num_experts_per_tok']}th and "
           f"next score lie within {limits['near_tie_margin']:g} (compared "
           f"under the program's own choice) {near:.4f} <= "
           f"{limits['near_tie_share']:g}"] = near <= limits["near_tie_share"]
    checks[f"share of tokens outside that margin whose choice differs from "
           f"the reference's, worst layer {max(differ):.2e} <= "
           f"{limits['differing_share']:g}"] = \
        max(differ) <= limits["differing_share"]

    got = errors_against(ref, loss, main, mtp, grads)
    timed = abs(first_loss - float(ref[0][0])) / abs(float(ref[0][0]))
    say(f"[reference] loss: reference {float(ref[0][0]):.6f}, the net's "
        f"{float(loss):.6f}, the timed program's first {first_loss:.6f}; "
        f"worst single gradient {got['grad_worst_name']} "
        f"{got['grad_worst']:.4f} of {got['n_grads']}")
    for what, value, key in (
            ("the timed program's first loss", timed, "loss"),
            ("the net's loss", got["loss"], "loss"),
            ("the two heads' logits, relative L2", got["logits"], "logits"),
            ("all gradients as one vector, relative L2", got["grads"],
             "grads"),
            ("the worst single parameter's gradient, relative L2",
             got["grad_worst"], "grad_worst")):
        checks[f"{what}: {value:.3e} <= {limits[key]:g}"] = \
            value <= limits[key]

    probe = limits.get("lower_precision_probe")
    if probe:
        # the reference again with every matmul operand rounded to the
        # nearest precision below the configuration's: it must be refused
        t = now()
        low = run_reference(jnp.dtype(probe))
        (low_loss, (low_main, low_mtp, _)), low_grads = low
        low_got = errors_against(ref, low_loss, low_main, low_mtp, low_grads)
        refused = [k for k in ("loss", "logits", "grads", "grad_worst")
                   if low_got[k] > limits[k]]
        say(f"[probe] the reference with operands rounded to {probe} "
            f"({now() - t:.1f} s): loss {low_got['loss']:.3e}, logits "
            f"{low_got['logits']:.3e}, gradients {low_got['grads']:.3e}, "
            f"worst gradient {low_got['grad_worst']:.3e} "
            f"({low_got['grad_worst_name']}); refused by: "
            f"{', '.join(refused) or 'NOTHING'}")
        checks[f"the reference in {probe} would be refused"] = bool(refused)


def run(job):
    import jax
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import amp, executor_cache
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon.nn.transformer_layers import moe_stats

    say, cell, config, model = (job["say"], job["cell"], job["config"],
                                job["model"])
    traffic, devices, seed = cell["traffic"], job["devices"], job["seed"]
    batch, pool_size = traffic["batch"], traffic["pool"]
    depth = traffic["queue_depth"]
    if len(devices) != 1:
        raise ValueError("this runner drives one chip")
    say(f"[setup] compile cache {executor_cache.ensure_compile_cache()}")

    # ---- set-up: all of it counts as set-up time
    mx.random.seed(fold_seed(seed))
    built = model.build(seed, config)
    net = built["net"]
    amp.convert_block(net, config["dtype"])
    step = make_fused_train_step(net, built["loss"], built["optimizer"],
                                 dict(built["optimizer_params"]))
    pool = [jax.device_put(model.make_batch(seed, i, batch, config, traffic),
                           devices[0])
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
    check_losses(losses, pool_size, model.uniform_loss(config), checks)
    failed = sum(1 for v in losses[1 + len(warm):1 + len(warm)
                                   + len(in_window)] if not math.isfinite(v))
    leaves = jax.tree_util.tree_leaves(
        (step.params, step.aux, step.opt_state))
    checks[f"all {len(leaves)} state leaves on the cell's device"] = \
        {frozenset(leaf.devices()) for leaf in leaves} == {frozenset(devices)}
    counters = moe_stats(step.aux)
    for name, c in counters.items():
        say(f"[moe] {name}: rows_held {c['rows_held']:.0f} of a buffer of "
            f"{c['buffer_rows']:.0f}, passes {c['passes']:.0f}, "
            f"load_max_over_mean {c['load_max_over_mean']:.3f}, "
            f"overflow_steps {c['overflow_steps']:.0f}")
    overflow = sum(c["overflow_steps"] for c in counters.values())
    checks[f"moe.overflow_steps {overflow:.0f} == 0 over {len(counters)} "
           f"routed layers and {len(losses)} steps"] = \
        bool(counters) and overflow == 0
    peak = device_memory_peak(devices[0])
    if job["trace"] and "reference" in cell:
        # free the train state first: the comparison needs the chip's memory
        step.params = step.aux = step.opt_state = None
        del step, leaves
        gc.collect()
        reference_comparison(job, built, pool[0], losses[0], checks, say)
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
        "cell": cell, "config": config, "model": model, "moe": counters,
    }
