"""The runner named by ``"runner": "train_vs_blockwise_reference_moe"``:
``train.py``'s set-up, window and record, with the loop and checks of the
two reference runners imported and nothing of them edited, for a
configuration that has routed layers *and* whose plain reference computes a
layer at a time because its float32 weights and gradients do not fit the chip
whole (``<config>.reference``: ``loss_and_grads(params, tokens, labels,
config, forced=, margin=, round_to=, fold=)`` with two heads' logits).

After the window it reads the routed layers' counters from the step's aux
state, as ``train_vs_reference`` does: no step may have needed a second pass
over the held experts' buffer.  On the traced run it makes the comparison
that decides ``correct``, at the timed sizes and the published widths,
against the reference in float32 at the highest matmul precision on the same
chip, as ``train_vs_blockwise_reference`` does.  **Of the timed program
itself**, read back after its first call and before its second: its first
loss; Adam's first moments against the reference's gradients (``grads``,
``grad_worst``); the change of every parameter against the reference's own
first AdamW step stored in the weight's dtype, as one vector relative to that
step (``update``: a state left unchanged reads 1).  **Of the same net outside
the step** (its initial weights, the same batch, dtype policy and
recomputation), forward only: its loss, both heads' logits and what its
routers chose.  Every gradient is held through the timed program's own first
moments, not through a second backward pass outside the step: that program
(the net's ``value_and_grad`` with the gradients as its outputs) compiled for
the chip and did not finish there (PERF.md section 7, PR 36).  Where a
router's ``k``-th and next score lie within ``near_tie_margin`` the reference
takes the program's own choice (``nn.record_routing``); a token outside the
margin whose experts differ is the program's error.  The limits are the
cell's (``reference`` in its file, each with its reason).
"""
import gc
import math

from chipbench.runners.train import (device_memory_peak, fold_seed, now,
                                     steady_loop, traced_steps)
from chipbench.runners.train_vs_blockwise_reference import (
    Differences, first_step_sums, stored_as)
from chipbench.runners.train_vs_reference import check_losses, rel_l2


def routing_checks(routing, forced, config, limits, checks):
    """The two checks of ``train_vs_reference`` on what the reference's
    routers did against the program's own choice."""
    import jax.numpy as jnp
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
           f"the reference's, worst of {len(differ)} routed layers "
           f"{max(differ):.2e} <= {limits['differing_share']:g}"] = \
        max(differ) <= limits["differing_share"]


def reference_comparison(job, built, batch, first_loss, after_first, checks,
                         say):
    """See the module's docstring.  Runs after the step's state is freed;
    ``after_first`` is ``(parameters, Adam's first moments)`` as the timed
    program's first call left them, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
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
    moved_to, moments = after_first

    def forward(p, x, y):
        with record_routing() as chosen:
            outs = apply(p, x, training=True)
        loss = loss_block(*map(NDArray, outs), NDArray(y))
        return jnp.mean(loss.data), outs, list(chosen)

    t = now()
    on_chip = jax.device_put(params, device)
    loss, (main, mtp), chosen = jax.jit(forward)(on_chip, tokens, labels)
    jax.block_until_ready(chosen)
    k = config["num_experts_per_tok"]
    chose_k = len(chosen) == len(routed) and all(
        c.shape[-1] == k for c in chosen)
    checks[f"every one of the {len(routed)} routers chose {k} experts a "
           "token"] = chose_k
    # where it did not, the reference goes by its own choice alone
    forced = dict(zip(routed, chosen)) if chose_k else {}
    say(f"[reference] the net's own loss, both heads' logits and its "
        f"{len(chosen)} routers' choices at its initial weights in "
        f"{now() - t:.1f} s")

    probe = limits.get("lower_precision_probe")
    kept = {}           # the float32 reference's gradients, on the host
    first = {what: Differences()
             for what in ("step_grads", "update", "rounding")}
    first_step = first_step_sums(reference, config["optimizer_params"])
    still = []          # left as they were, though the reference moves them

    def against_the_net(part):
        for name, ref_grad in part.items():
            sums = first_step(on_chip[name], ref_grad, moved_to.pop(name),
                              moments.pop(name))
            if float(sums.pop("moved")) == 0.0 < float(sums["update"][1]):
                still.append(name)
            for what, pair in sums.items():
                first[what].add_sums(name, *pair)
            if probe:
                kept[name] = onp.asarray(ref_grad)

    t = now()
    (ref_loss, (ref_main, ref_mtp, routing)), _ = reference.loss_and_grads(
        on_chip, tokens, labels, config, forced=forced,
        margin=limits["near_tie_margin"], fold=against_the_net)
    step_grads, update, rounding = (first[what].summary() for what in (
        "step_grads", "update", "rounding"))
    say(f"[reference] float32 reference at the highest matmul precision, a "
        f"layer at a time, on {device} in {now() - t:.1f} s")
    checks[f"the reference ran on {device}"] = \
        ref_main.devices() == {device}
    checks[f"every one of the timed program's {update['n']} parameters and "
           "first moments met the reference's"] = \
        not moved_to and not moments
    checks[f"the timed program's first call moved every parameter that the "
           f"reference's step moves"
           f"{': not ' + ', '.join(still) if still else ''}"] = not still
    if forced:
        routing_checks(routing, forced, config, limits, checks)
    ref_loss = float(ref_loss)
    logits = max(rel_l2(main, ref_main), rel_l2(mtp, ref_mtp))
    say(f"[reference] loss: reference {ref_loss:.6f}, the net's "
        f"{float(loss):.6f}, the timed program's first {first_loss:.6f}; "
        f"worst single one of the timed step's {step_grads['n']} gradients "
        f"{step_grads['worst_name']} {step_grads['worst']:.4f}")
    say(f"[reference] the timed step's change of the parameters against "
        f"the reference's first AdamW step stored in the weights' dtype: "
        f"{update['all']:.4f} as one vector (a state left unchanged reads "
        f"1), median parameter {update['median']:.4f}, worst "
        f"{update['worst_name']} {update['worst']:.4f}; storing the "
        f"reference's step in the weights' dtype alone moves it by "
        f"{rounding['all']:.4f}")
    for what, value, key in (
            ("the timed program's first loss",
             abs(first_loss - ref_loss) / abs(ref_loss), "loss"),
            ("the net's loss", abs(float(loss) - ref_loss) / abs(ref_loss),
             "loss"),
            ("the two heads' logits, relative L2", logits, "logits"),
            ("the timed step's own gradients (its first moments) as one "
             "vector, relative L2", step_grads["all"], "grads"),
            ("the worst single one of the timed step's own gradients, "
             "relative L2", step_grads["worst"], "grad_worst"),
            ("the timed step's change of the parameters against the "
             "reference's AdamW step, relative L2", update["all"],
             "update")):
        checks[f"{what}: {value:.3e} <= {limits[key]:g}"] = \
            value <= limits[key]
    del main, mtp

    if probe:
        # the reference again with every matmul operand rounded to the
        # nearest precision below the configuration's: it must be refused
        low_seen, low_update = Differences(), Differences()
        step_of = lambda w, g: reference.adamw_first_step(
            w, g, **config["optimizer_params"])[1]

        @jax.jit
        def updates(w0, low_grad, ref_grad):
            w = w0.astype(jnp.float32)
            change = lambda g: stored_as(step_of(w, g), w0.dtype) - w
            low, ref = change(low_grad), change(ref_grad)
            return jnp.sum(jnp.square(low - ref)), jnp.sum(jnp.square(ref))

        def against_the_reference(part):
            for name, low_grad in part.items():
                ref_grad = kept.pop(name)
                low_seen.add(name, low_grad, ref_grad)
                low_update.add_sums(name, *updates(on_chip[name], low_grad,
                                                   ref_grad))

        t = now()
        (low_loss, (low_main, low_mtp, _)), _ = reference.loss_and_grads(
            on_chip, tokens, labels, config, forced=forced,
            margin=limits["near_tie_margin"], round_to=jnp.dtype(probe),
            fold=against_the_reference)
        worst = low_seen.summary()
        low = {"loss": abs(float(low_loss) - ref_loss) / abs(ref_loss),
               "logits": max(rel_l2(low_main, ref_main),
                             rel_l2(low_mtp, ref_mtp)),
               "grads": worst["all"], "grad_worst": worst["worst"],
               "update": low_update.summary()["all"]}
        refused = [k for k in low if low[k] > limits[k]]
        say(f"[probe] the reference with operands rounded to {probe} "
            f"({now() - t:.1f} s): loss {low['loss']:.3e}, logits "
            f"{low['logits']:.3e}, gradients {low['grads']:.3e}, worst "
            f"gradient {low['grad_worst']:.3e} ({worst['worst_name']}), its "
            f"AdamW step against the float32 reference's "
            f"{low['update']:.3e}; refused by: "
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
    t_net = now()
    step = make_fused_train_step(net, built["loss"], built["optimizer"],
                                 dict(built["optimizer_params"]))
    pool = [jax.device_put(model.make_batch(seed, i, batch, config, traffic),
                           devices[0])
            for i in range(pool_size)]
    t_built = now()
    say(f"[setup] net initialised on the host "
        f"{t_net - job['process_start']:.1f} s after process start; step "
        f"and a pool of {pool_size} batches of {batch} built and placed "
        f"{t_built - t_net:.1f} s later")
    first_loss = step(*pool[0])
    first_loss.block_until_ready()
    compile_s = now() - t_built
    after_first = None
    if job["trace"] and "reference" in cell:
        # what the timed program's first call left, before its second call
        # takes it: the parameters and Adam's first moments, to the host
        after_first = jax.device_get((step.params, step.opt_state["m"]))
        say(f"[setup] the state after the first call read back in "
            f"{now() - t_built - compile_s:.1f} s (traced runs only)")
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
    limit = (devices[0].memory_stats() or {}).get("bytes_limit")
    if limit:
        say(f"[memory] peak {peak / 2**30:.3f} GiB of the device's "
            f"{limit / 2**30:.3f} GiB")
    if job["trace"] and "reference" in cell:
        # free the train state first: the comparison needs the chip's memory
        step.params = step.aux = step.opt_state = None
        del step, leaves
        gc.collect()
        reference_comparison(job, built, pool[0], losses[0], after_first,
                             checks, say)
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
