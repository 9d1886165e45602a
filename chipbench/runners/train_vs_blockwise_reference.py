"""The runner named by ``"runner": "train_vs_blockwise_reference"``:
``train.py``'s set-up, window and record, with its loop and checks imported
and nothing of it edited, for a configuration whose plain reference
(``<config>.reference``) computes a block at a time because its float32
weights and gradients do not fit the chip whole: ``loss_and_grads(params,
tokens, labels, config, round_to=, fold=)`` hands each block's gradients to
``fold`` as soon as they exist and keeps none.

On the traced run it makes the comparison that decides ``correct``, at the
timed sizes and the published widths, against the reference in float32 at
the highest matmul precision on the same chip.  **Of the timed program
itself**, read back after its first call and before its second: its first
loss; Adam's first moment, which after one step from zero is ``(1 − β1)``
times the gradient the fused step computed, against the reference's
gradients (the limits ``grads`` and ``grad_worst``); and the change of every
parameter against the reference's own first AdamW step
(``reference.adamw_first_step`` from its float32 gradient, stored in the
weight's dtype), as one vector relative to that step, so that a state left
unchanged reads 1 (the limit ``update``; how far that storage alone moves
the reference's step is printed beside it, bound by nothing).  **Of the
same net outside the step** (its initial weights, the same batch, dtype
policy and recomputation): the logits and the gradient of every parameter.  The
reference's gradients go to the host as they come (float32), so that the
probe — the reference again with every matmul operand rounded to a lower
precision — can be held to them the same way.  The limits are the cell's
(``reference`` in its file, each with its reason).  All of it runs after
the window and after the train state is freed, but the read-back.
"""
import gc
import math

from chipbench.runners.train import (device_memory_peak, fold_seed, now,
                                     steady_loop, traced_steps)
from chipbench.runners.train_vs_reference import check_losses, rel_l2


class Differences:
    """``|a − b|²`` and ``|b|²`` of named pairs of tensors, summed as the
    pairs come (``b`` is the reference's side), and the numbers the limits
    bound: the relative L2 error of all of them taken as one vector, the
    largest of a single one with its name, and the median one."""

    def __init__(self):
        import jax
        import jax.numpy as jnp
        self.off, self.size = {}, {}
        self._pair = jax.jit(lambda a, b: (
            jnp.sum(jnp.square(a.astype(jnp.float32) - b)),
            jnp.sum(jnp.square(b))))

    def add(self, name, a, b):
        self.add_sums(name, *self._pair(a, b))

    def add_sums(self, name, off, size):
        self.off[name], self.size[name] = float(off), float(size)

    def summary(self):
        # a pair that is zero on both sides agrees; zero on the reference's
        # side alone is as wrong as can be
        each = sorted((math.sqrt(self.off[n] / self.size[n])
                       if self.size[n] > 0.0
                       else (math.inf if self.off[n] > 0.0 else 0.0), n)
                      for n in self.off)
        return {"all": math.sqrt(sum(self.off.values())
                                 / sum(self.size.values())),
                "worst": each[-1][0], "worst_name": each[-1][1],
                "median": each[len(each) // 2][0], "n": len(each)}


def stored_as(x, dtype):
    """Float32 ``x`` rounded to what ``dtype`` can hold, still float32.
    ``reduce_precision`` and not a pair of converts, which XLA drops on a
    TPU (it read a bfloat16 weight's step as stored exactly: PERF.md
    section 6, PR 34)."""
    import jax
    import jax.numpy as jnp
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def first_step_sums(reference, optimizer_params):
    """The jitted comparison of one parameter's first step: ``(w0, the
    reference's float32 gradient, w1, m1)`` → the sums of squares behind
    three relative errors and ``|w1 − w0|²``.  ``w0`` is the initial weight,
    ``w1`` and ``m1`` what the timed program's first call left of it and of
    Adam's first moment.  One fusion a leaf: nothing of a leaf's size is
    kept."""
    import jax
    import jax.numpy as jnp

    def sums(a, b):
        return jnp.sum(jnp.square(a - b)), jnp.sum(jnp.square(b))

    def compare(w0, grad, w1, m1):
        w = w0.astype(jnp.float32)
        ref_m, ref_w = reference.adamw_first_step(w, grad,
                                                  **optimizer_params)
        stored = stored_as(ref_w, w0.dtype) - w
        moved = w1.astype(jnp.float32) - w
        return {"step_grads": sums(m1.astype(jnp.float32), ref_m),
                "update": sums(moved, stored),
                "rounding": sums(stored, ref_w - w),
                "moved": jnp.sum(jnp.square(moved))}

    return jax.jit(compare)


def reference_comparison(job, built, batch, first_loss, after_first, checks,
                         say):
    """See the module's docstring.  Runs after the step's state is freed;
    ``after_first`` is ``(parameters, Adam's first moments)`` as the timed
    program's first call left them, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as onp
    from incubator_mxnet_tpu.ndarray import NDArray
    limits, config = job["cell"]["reference"], job["config"]
    reference, device = job["model"].reference, job["devices"][0]
    net, loss_block = built["net"], built["loss"]
    params, apply = net.functional()
    tokens, labels = batch
    moved_to, moments = after_first

    def system(p, x, y):
        def loss_of(p):
            out = apply(p, x, training=True)
            return jnp.mean(loss_block(NDArray(out), NDArray(y)).data), out
        return jax.value_and_grad(loss_of, has_aux=True)(p)

    t = now()
    on_chip = jax.device_put(params, device)
    (loss, logits), grads = jax.jit(system)(on_chip, tokens, labels)
    jax.block_until_ready(grads)
    say(f"[reference] the net's own loss, logits and {len(grads)} gradients "
        f"at its initial weights in {now() - t:.1f} s")

    probe = limits.get("lower_precision_probe")
    kept = {}           # the float32 reference's gradients, on the host

    seen = Differences()
    first = {what: Differences()
             for what in ("step_grads", "update", "rounding")}
    first_step = first_step_sums(reference, config["optimizer_params"])
    still = []          # left as they were, though the reference moves them

    def against_the_net(part):
        for name, ref_grad in part.items():
            seen.add(name, grads.pop(name), ref_grad)
            sums = first_step(on_chip[name], ref_grad, moved_to.pop(name),
                              moments.pop(name))
            if float(sums.pop("moved")) == 0.0 < float(sums["update"][1]):
                still.append(name)
            for what, pair in sums.items():
                first[what].add_sums(name, *pair)
            if probe:
                kept[name] = onp.asarray(ref_grad)

    t = now()
    (ref_loss, ref_logits), _ = reference.loss_and_grads(
        on_chip, tokens, labels, config, fold=against_the_net)
    got = seen.summary()
    step_grads, update, rounding = (first[what].summary() for what in (
        "step_grads", "update", "rounding"))
    say(f"[reference] float32 reference at the highest matmul precision, a "
        f"block at a time, on {device} in {now() - t:.1f} s")
    checks[f"the reference ran on {device}"] = \
        ref_logits.devices() == {device}
    checks[f"every one of the net's {got['n']} gradients met the "
           "reference's"] = not grads
    checks[f"every one of the timed program's {update['n']} parameters and "
           "first moments met the reference's"] = \
        not moved_to and not moments
    checks[f"the timed program's first call moved every parameter that the "
           f"reference's step moves"
           f"{': not ' + ', '.join(still) if still else ''}"] = not still
    ref_loss = float(ref_loss)
    timed = abs(first_loss - ref_loss) / abs(ref_loss)
    say(f"[reference] loss: reference {ref_loss:.6f}, the net's "
        f"{float(loss):.6f}, the timed program's first {first_loss:.6f}; "
        f"worst single gradient {got['worst_name']} {got['worst']:.4f} of "
        f"{got['n']}, of the timed step's {step_grads['worst_name']} "
        f"{step_grads['worst']:.4f}")
    say(f"[reference] the timed step's change of the parameters against "
        f"the reference's first AdamW step stored in the weights' dtype: "
        f"{update['all']:.4f} as one vector (a state left unchanged reads "
        f"1), median parameter {update['median']:.4f}, worst "
        f"{update['worst_name']} {update['worst']:.4f}; storing the "
        f"reference's step in the weights' dtype alone moves it by "
        f"{rounding['all']:.4f}")
    for what, value, key in (
            ("the timed program's first loss", timed, "loss"),
            ("the net's loss", abs(float(loss) - ref_loss) / abs(ref_loss),
             "loss"),
            ("the logits, relative L2", rel_l2(logits, ref_logits),
             "logits"),
            ("all gradients as one vector, relative L2", got["all"],
             "grads"),
            ("the worst single parameter's gradient, relative L2",
             got["worst"], "grad_worst"),
            ("the timed step's own gradients (its first moments) as one "
             "vector, relative L2", step_grads["all"], "grads"),
            ("the worst single one of the timed step's own gradients, "
             "relative L2", step_grads["worst"], "grad_worst"),
            ("the timed step's change of the parameters against the "
             "reference's AdamW step, relative L2", update["all"],
             "update")):
        checks[f"{what}: {value:.3e} <= {limits[key]:g}"] = \
            value <= limits[key]
    del logits

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
        (low_loss, low_logits), _ = reference.loss_and_grads(
            on_chip, tokens, labels, config, round_to=jnp.dtype(probe),
            fold=against_the_reference)
        worst = low_seen.summary()
        low = {"loss": abs(float(low_loss) - ref_loss) / abs(ref_loss),
               "logits": rel_l2(low_logits, ref_logits),
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
        "cell": cell, "config": config, "model": model,
    }
