"""Perf probe: honest step timing on the real chip.

Timing discipline: every measurement chains steps through a carried
value and ends with a host readback INSIDE the timed region — the
device has to finish before its answer can be read.  (On a v5e under
the installed JAX `block_until_ready` closes a window just as well:
`chip_smoke.py` times both and checks they agree.)

Modes:
  python scripts/perf_probe.py layout   # raw-JAX NCHW vs NHWC conv stack
  python scripts/perf_probe.py fused    # framework fused ResNet-50 step
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax
import jax.numpy as jnp
import numpy as onp

from incubator_mxnet_tpu.executor_cache import ensure_compile_cache

ensure_compile_cache()   # the probes' raw jax.jit compiles share the cache

PEAK = 197e12  # v5e bf16 (multiply-add = 2 flops)
# ResNet-50 fwd = 4.089 GMACs = 8.178e9 true flops/img; train ~ 3x fwd.
# The MAC/flop convention split understated every MFU before the
# round-4 audit by exactly 2x (see bench.py TRAIN_FLOPS_PER_IMG).
R50_FWD_FLOPS = 2 * 4.089e9
R50_TRAIN_FLOPS = 3 * R50_FWD_FLOPS


def sync(tree):
    """Host readback of one element — the only reliable sync here."""
    leaf = jax.tree_util.tree_leaves(tree)[0]
    onp.asarray(jax.device_get(leaf.ravel()[:1]).astype(jnp.float32))


def timeit(fn, carry, steps=20, warmup=4):
    """fn(*carry) -> new carry of the same structure (donation-safe)."""
    for _ in range(warmup):
        carry = fn(*carry)
    sync(carry)
    t0 = time.perf_counter()
    for _ in range(steps):
        carry = fn(*carry)
    sync(carry)  # chains through the carry: waits for all steps
    return (time.perf_counter() - t0) / steps


def conv_stack_params(key, layout):
    """ResNet-50-ish conv tower: channels and spatial sizes of the real net."""
    cfg = [  # (cin, cout, k, stride, h)
        (3, 64, 7, 2, 224),
        (64, 256, 3, 1, 56), (256, 256, 3, 1, 56), (256, 256, 3, 1, 56),
        (256, 512, 3, 2, 56), (512, 512, 3, 1, 28), (512, 512, 3, 1, 28),
        (512, 1024, 3, 2, 28), (1024, 1024, 3, 1, 14),
        (1024, 1024, 3, 1, 14),
        (1024, 2048, 3, 2, 14), (2048, 2048, 3, 1, 7),
    ]
    params = []
    flops = 0
    for i, (ci, co, k, s, h) in enumerate(cfg):
        key, sub = jax.random.split(key)
        if layout == "NCHW":
            w = jax.random.normal(sub, (co, ci, k, k), jnp.bfloat16) * 0.05
        else:
            w = jax.random.normal(sub, (k, k, ci, co), jnp.bfloat16) * 0.05
        params.append(w)
        ho = h // s
        flops += 2 * ci * co * k * k * ho * ho
    return params, cfg, flops


def make_stack(layout, cfg):
    from jax import lax

    dn_str = ("NCHW", "OIHW", "NCHW") if layout == "NCHW" else \
        ("NHWC", "HWIO", "NHWC")

    def fwd(params, x):
        y = x
        for w, (ci, co, k, s, h) in zip(params, cfg):
            dn = lax.conv_dimension_numbers(y.shape, w.shape, dn_str)
            y = lax.conv_general_dilated(
                y, w, (s, s), [(k // 2, k // 2)] * 2, dimension_numbers=dn)
            y = jax.nn.relu(y)
        return jnp.mean(y.astype(jnp.float32))

    def step(params, x):
        loss, g = jax.value_and_grad(fwd)(params, x)
        new_params = jax.tree_util.tree_map(
            lambda p, gg: p - 0.0001 * gg.astype(p.dtype), params, g)
        return new_params, x

    return jax.jit(step, donate_argnums=(0,))


def probe_layout():
    bs = int(os.environ.get("PROBE_BS", "128"))
    for layout in ("NCHW", "NHWC"):
        key = jax.random.PRNGKey(0)
        params, cfg, flops = conv_stack_params(key, layout)
        shape = (bs, 3, 224, 224) if layout == "NCHW" else (bs, 224, 224, 3)
        x = jax.random.normal(key, shape, jnp.bfloat16)
        step = make_stack(layout, cfg)
        dt = timeit(step, (params, x))
        tf = 3 * flops * bs / dt / 1e12  # fwd+bwd ~ 3x fwd FLOPs
        print(f"{layout}: {dt * 1e3:8.2f} ms/step  ~{tf:6.1f} TFLOP/s "
              f"({100 * tf * 1e12 / PEAK:.1f}% of peak)", flush=True)


def probe_fused():
    bs = int(os.environ.get("PROBE_BS", "128"))
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, amp
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    # stage ALL eager setup on the CPU backend: every eager op on the
    # chip is a small compile of its own
    accel = jax.devices()[0]
    cpu0 = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu0):
        net = vision.resnet50_v1()
        net.initialize(ctx=mx.cpu())
        net(nd.random.uniform(shape=(1, 3, 32, 32)))
        amp.convert_block(net, "bfloat16")
        step = make_fused_train_step(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
        x = jnp.asarray(onp.random.rand(bs, 3, 224, 224), jnp.bfloat16)
        y = jnp.asarray(onp.random.randint(0, 1000, (bs,)), jnp.int32)
    put = lambda t: jax.device_put(t, accel)  # noqa: E731
    step.params = jax.tree_util.tree_map(put, step.params)
    step.aux = jax.tree_util.tree_map(put, step.aux)
    step.opt_state = jax.tree_util.tree_map(put, step.opt_state)
    x, y = put(x), put(y)

    t0 = time.perf_counter()
    loss = step(x, y)
    float(loss)
    print(f"compile+first: {time.perf_counter() - t0:.1f}s", flush=True)
    for _ in range(3):
        loss = step(x, y)
    float(loss)
    steps = 20
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, y)
    lv = float(loss)
    dt = (time.perf_counter() - t0) / steps
    ips = bs / dt
    mfu = 100 * ips * R50_TRAIN_FLOPS / PEAK
    print(f"fused bs={bs}: {dt * 1e3:.2f} ms/step  {ips:.0f} img/s  "
          f"MFU {mfu:.1f}%  loss {lv:.3f}", flush=True)


def probe_matmul():
    """MXU sanity: peak bf16 matmul throughput."""
    for n in (4096, 8192):
        k = jax.random.PRNGKey(0)
        a = jax.random.normal(k, (n, n), jnp.bfloat16)
        b = jax.random.normal(k, (n, n), jnp.bfloat16)

        @jax.jit
        def mm(a, b):
            # chain 8 matmuls so dispatch overhead amortizes
            x = a
            for _ in range(8):
                x = (x @ b) * (1.0 / n)
            return x, b

        dt = timeit(lambda a, b: mm(a, b), (a, b), steps=10)
        tf = 8 * 2 * n ** 3 / dt / 1e12
        print(f"matmul {n}: {dt * 1e3:8.2f} ms  ~{tf:6.1f} TFLOP/s "
              f"({100 * tf * 1e12 / PEAK:.1f}% of peak)", flush=True)


def probe_conv1():
    """Isolate single-conv efficiency: one conv shape, chained, like the
    matmul probe — separates conv-kernel quality from tower effects."""
    from jax import lax
    bs = int(os.environ.get("PROBE_BS", "128"))
    cases = [  # (cin, cout, k, stride, h, layout)
        (512, 512, 3, 1, 28, "NCHW"),
        (512, 512, 3, 1, 28, "NHWC"),
        (256, 256, 3, 1, 56, "NHWC"),
        (2048, 2048, 3, 1, 7, "NHWC"),
        (64, 64, 3, 1, 112, "NHWC"),
        (3, 64, 7, 2, 224, "NHWC"),
    ]
    for ci, co, k, s, h, layout in cases:
        key = jax.random.PRNGKey(0)
        if layout == "NCHW":
            x = jax.random.normal(key, (bs, ci, h, h), jnp.bfloat16)
            w = jax.random.normal(key, (co, ci, k, k), jnp.bfloat16) * 0.02
            dn_str = ("NCHW", "OIHW", "NCHW")
        else:
            x = jax.random.normal(key, (bs, h, h, ci), jnp.bfloat16)
            w = jax.random.normal(key, (k, k, ci, co), jnp.bfloat16) * 0.02
            dn_str = ("NHWC", "HWIO", "NHWC")
        reps = 8 if ci == co and s == 1 else 1

        @jax.jit
        def f(x, w, _dn_str=dn_str, _reps=reps, _k=k, _s=s):
            y = x
            for _ in range(_reps):
                dn = lax.conv_dimension_numbers(y.shape, w.shape, _dn_str)
                y = lax.conv_general_dilated(
                    y, w, (_s, _s), [(_k // 2, _k // 2)] * 2,
                    dimension_numbers=dn)
                y = y * (1.0 / _k)
            return y

        # warm up, then time 10 dispatches and sync once at the end (the
        # final host readback waits for the whole queued sequence)
        for _ in range(2):
            y = f(x, w)
        sync(y)
        t0 = time.perf_counter()
        for _ in range(10):
            y = f(x, w)
        sync(y)
        dt = (time.perf_counter() - t0) / 10
        ho = h // s
        fl = reps * 2 * ci * co * k * k * ho * ho * bs
        tf = fl / dt / 1e12
        print(f"{layout} {ci:4d}->{co:4d} k{k} s{s} {h:3d}px x{reps}: "
              f"{dt * 1e3:7.2f} ms  ~{tf:6.1f} TFLOP/s "
              f"({100 * tf * 1e12 / PEAK:.1f}% of peak)", flush=True)


def probe_ablate():
    """Decompose the fused-step time into three measurements — full
    train step, train step with eval-mode BN (no batch-stat
    reductions), forward only — to attribute the 15%-MFU full-step gap
    (the chained conv kernels themselves reach 84-91% of peak; see
    docs/performance.md round-4 findings)."""
    bs = int(os.environ.get("PROBE_BS", "128"))
    import incubator_mxnet_tpu as mx
    from incubator_mxnet_tpu import nd, gluon, amp
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.gluon.model_zoo import vision

    mx.random.seed(0)
    accel = jax.devices()[0]
    cpu0 = jax.local_devices(backend="cpu")[0]
    with jax.default_device(cpu0):
        net = vision.resnet50_v1()
        net.initialize(ctx=mx.cpu())
        net(nd.random.uniform(shape=(1, 3, 32, 32)))
        amp.convert_block(net, "bfloat16")
        step = make_fused_train_step(
            net, gluon.loss.SoftmaxCrossEntropyLoss(), "sgd",
            {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
        _, apply_fn = net.functional()
        x = jnp.asarray(onp.random.rand(bs, 3, 224, 224), jnp.bfloat16)
        y = jnp.asarray(onp.random.randint(0, 1000, (bs,)), jnp.int32)
    put = lambda t: jax.device_put(t, accel)  # noqa: E731
    params = jax.tree_util.tree_map(put, step.params)
    aux = jax.tree_util.tree_map(put, step.aux)
    opt_state = jax.tree_util.tree_map(put, step.opt_state)
    x, y = put(x), put(y)
    flops_train = R50_TRAIN_FLOPS * bs
    flops_fwd = R50_FWD_FLOPS * bs

    failures = []

    def timed(name, fn, carry, flops, steps=10):
        # one measurement failing must not lose the others — each is
        # independently valuable.  Failures are still FAILURES: the
        # process exits non-zero.
        try:
            dt = timeit(fn, carry, steps=steps, warmup=3)
        except Exception as e:  # mxlint: allow-broad-except(probe harness: the failure is printed and recorded, the sweep continues)
            print(f"{name:24s} FAILED: {type(e).__name__}: "
                  f"{str(e)[:120]}", flush=True)
            failures.append(name)
            return None
        print(f"{name:24s} {dt * 1e3:8.2f} ms  "
              f"{100 * flops / dt / PEAK:5.1f}% MFU-equiv", flush=True)
        return dt

    # (a) full train step (params chained through carry).  The step fn
    #     DONATES params/aux/opt_state (fuse.py donate_argnums), so it
    #     gets its own copies — the originals must survive for (b)/(c).
    def full(p, a, o, x, y):
        key = jax.random.PRNGKey(0)
        p2, a2, o2, loss = step._step_fn(p, a, o, x, y, key)
        return p2, a2, o2, x, y
    copy = lambda tree: jax.tree_util.tree_map(jnp.array, tree)  # noqa
    timed("full train step", full,
          (copy(params), copy(aux), copy(opt_state), x, y), flops_train)

    # (b) fwd+bwd+sgd WITHOUT BatchNorm batch stats (use_global_stats
    #     analog: training=False apply → moving stats, no reductions)
    def loss_eval(p, x, y):
        out = apply_fn(p, x, training=False)
        if isinstance(out, tuple):
            out = out[0]
        lp = jax.nn.log_softmax(out.astype(jnp.float32))
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], 1))

    @jax.jit
    def train_nobn(p, x, y):
        loss, g = jax.value_and_grad(loss_eval)(p, x, y)
        p2 = jax.tree_util.tree_map(
            lambda w, gg: (w - 0.1 * gg.astype(w.dtype)), p, g)
        return p2, x, y
    pa = {**params, **aux}
    timed("train, eval-mode BN", train_nobn, (pa, x, y), flops_train)

    # (c) forward only, eval-mode BN
    @jax.jit
    def fwd_loop(p, x):
        out = apply_fn(p, x, training=False)
        if isinstance(out, tuple):
            out = out[0]
        # chain: feed a scalar of the output back into x so steps serialize
        return x + out.mean().astype(x.dtype) * 0, p

    def fwd_carry(x, p):
        x2, _ = fwd_loop(p, x)
        return x2, p
    timed("fwd only (eval BN)", fwd_carry, (x, pa), flops_fwd)
    if failures:
        sys.exit(f"ablate: {len(failures)} measurement(s) failed: "
                 f"{failures}")



def probe_stem():
    """ResNet stem experiment: 7x7/s2 conv on (N,3,224,224) vs the
    space-to-depth equivalent (4x4/s1 conv on (N,12,112,112) with a
    transformed kernel — the MLPerf TPU ResNet trick).  The C=3 input
    packs poorly onto the 128-lane MXU; s2d raises the contraction
    density 4x.  Prints a numeric-equivalence check, then timings."""
    from jax import lax
    bs = int(os.environ.get("PROBE_BS", "128"))
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (bs, 3, 224, 224), jnp.bfloat16)
    w = jax.random.normal(key, (64, 3, 7, 7), jnp.bfloat16) * 0.05

    def stem_plain(x, w):
        dn = lax.conv_dimension_numbers(x.shape, w.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        return lax.conv_general_dilated(x, w, (2, 2), [(3, 3), (3, 3)],
                                        dimension_numbers=dn)

    def s2d(x):
        # (N, C, H, W) -> (N, 4C, H/2, W/2), block-major (dy, dx)
        n, c, h, wd = x.shape
        y = x.reshape(n, c, h // 2, 2, wd // 2, 2)
        return y.transpose(0, 1, 3, 5, 2, 4).reshape(n, c * 4, h // 2,
                                                     wd // 2)

    def make_w2(w):
        # embed the 7x7 kernel (pad 3) into the s2d domain: output pixel
        # (i, j) of the plain stem reads input rows 2i-3..2i+3 — in s2d
        # coordinates, rows i-2..i+1 of each parity plane. A 4x4 kernel
        # over 4 parity planes with offset -2 covers exactly that span.
        # Built in host numpy: 49 eager scatter dispatches on the chip
        # are 49 small compiles.
        o, c, _, _ = w.shape
        w_host = onp.asarray(jax.device_get(w).astype(jnp.float32))
        w8 = onp.zeros((o, c, 2, 2, 4, 4), onp.float32)
        for ky in range(7):
            for kx in range(7):
                # plain: input row r = 2i + ky - 3; decompose r = 2q + p:
                # parity p = (ky - 3) % 2, q-offset tap
                # t = (ky - 3 - p) // 2 + 2 in [0, 4)
                py, ty = (ky - 3) % 2, ((ky - 3) - ((ky - 3) % 2)) // 2 + 2
                px, tx = (kx - 3) % 2, ((kx - 3) - ((kx - 3) % 2)) // 2 + 2
                w8[:, :, py, px, ty, tx] = w_host[:, :, ky, kx]
        return jnp.asarray(w8.reshape(o, c * 4, 4, 4), w.dtype)

    def stem_s2d_pre(xs, w2):
        dn = lax.conv_dimension_numbers(xs.shape, w2.shape,
                                        ("NCHW", "OIHW", "NCHW"))
        # q-offset -2..1 relative to output pixel i -> pad (2, 1)
        return lax.conv_general_dilated(xs, w2, (1, 1), [(2, 1), (2, 1)],
                                        dimension_numbers=dn)

    def stem_s2d(x, w2):
        return stem_s2d_pre(s2d(x), w2)

    w2 = make_w2(w)
    diff = jax.jit(lambda a, b, c: jnp.max(jnp.abs(
        stem_plain(a, b) - stem_s2d(a, c))))
    err = float(diff(x[:2].astype(jnp.float32), w.astype(jnp.float32),
                     w2.astype(jnp.float32)))
    print(f"s2d equivalence max|diff| = {err:.2e} (fp32)", flush=True)
    if err > 1e-3:
        print("NOT equivalent — do not use", flush=True)
        return

    # pre-transform the input once: the MLPerf trick folds s2d into the
    # data pipeline, so the conv is timed on (N,12,112,112) directly;
    # the conv+transform variant is also timed for the in-graph case
    xs = jax.jit(s2d)(x)

    flops = 2 * 3 * 64 * 49 * 112 * 112 * bs
    for name, fn, args in (("stem 7x7/s2 plain", stem_plain, (x, w)),
                           ("s2d conv+transform", stem_s2d, (x, w2)),
                           ("s2d conv (pre-s2d)", stem_s2d_pre, (xs, w2))):
        # serialize steps by feeding a (numerically negligible) function
        # of the output back into the carried input
        jfn = jax.jit(lambda a, b, _f=fn: (
            a + (_f(a, b).ravel()[0] * 1e-20).astype(a.dtype), b))
        dt = timeit(lambda a, b: jfn(a, b), args, steps=10, warmup=3)
        print(f"{name:20s} {dt * 1e3:7.2f} ms  "
              f"~{flops / dt / 1e12:5.1f} TFLOP/s "
              f"({100 * flops / dt / PEAK:.1f}% of peak)", flush=True)


def probe_raw(max_stages=None):
    """Attainable-ceiling reference: a hand-written bf16 ResNet-50
    train step in raw jnp/lax (PROBE_LAYOUT=NHWC|NCHW) — no framework,
    BN stats one-pass in f32, SGD-momentum epilogue.  If this also
    lands at ~15% MFU the gap is the platform/XLA; if it is much
    faster, the gap is in our graph.

    max_stages (stages mode): truncate after that many residual stages
    (0 = stem+pool only) with a global-pool head, so successive deltas
    localize the step time per stage."""
    from jax import lax
    bs = int(os.environ.get("PROBE_BS", "128"))
    remat = os.environ.get("PROBE_REMAT", "0") == "1"
    bn_batch_stats = os.environ.get("PROBE_BN", "batch") == "batch"
    fused_blk = os.environ.get("PROBE_FUSED", "0") == "1"
    layout = os.environ.get("PROBE_LAYOUT", "NHWC").upper()
    if layout not in ("NHWC", "NCHW"):
        sys.exit(f"PROBE_LAYOUT must be NHWC or NCHW, got {layout!r}")
    nhwc = layout == "NHWC"
    if fused_blk:
        if not nhwc:
            sys.exit("PROBE_FUSED=1 needs PROBE_LAYOUT=NHWC (the fused "
                     "matmul kernels read channel-minor [M, C] views)")
        if not bn_batch_stats:
            sys.exit("PROBE_FUSED=1 needs PROBE_BN=batch: the fused "
                     "kernels exist to absorb batch-stat traffic; "
                     "eval-BN has no stats pass to fuse")
        # the A/B must exercise the kernels even before a manifest exists
        os.environ.setdefault("MXNET_USE_PALLAS", "1")
        from incubator_mxnet_tpu.ops import fused_block as fb
    CH = -1 if nhwc else 1                     # channel axis
    RED = (0, 1, 2) if nhwc else (0, 2, 3)     # BN reduce axes

    key = jax.random.PRNGKey(0)
    stages = [(256, 64, 3), (512, 128, 4), (1024, 256, 6), (2048, 512, 3)]
    if max_stages is not None:
        stages = stages[:max_stages]
    head_c = stages[-1][0] if stages else 64

    def conv(x, w, s=1):
        k = w.shape[0 if nhwc else 2]
        dn = lax.conv_dimension_numbers(
            x.shape, w.shape,
            ("NHWC", "HWIO", "NHWC") if nhwc else ("NCHW", "OIHW", "NCHW"))
        return lax.conv_general_dilated(x, w, (s, s),
                                        [(k // 2, k // 2)] * 2,
                                        dimension_numbers=dn)

    def bn(x, p, training):
        g, b = p
        if training and bn_batch_stats:
            mean = jnp.mean(x, RED, dtype=jnp.float32)
            meansq = jnp.mean(jnp.square(x), RED, dtype=jnp.float32)
            var = jnp.maximum(meansq - jnp.square(mean), 0.0)
        else:
            mean = jnp.zeros(x.shape[CH], jnp.float32)
            var = jnp.ones(x.shape[CH], jnp.float32)
        scale = (g * lax.rsqrt(var + 1e-5)).astype(x.dtype)
        bias = (b - mean * g * lax.rsqrt(var + 1e-5)).astype(x.dtype)
        bcast = [1] * x.ndim
        bcast[CH] = x.shape[CH]
        return x * scale.reshape(bcast) + bias.reshape(bcast)

    def init():
        params = {}
        k = [key]

        def mk(name, k_, ci, co, scale=0.05):
            k[0], sub = jax.random.split(k[0])
            shape = (k_, k_, ci, co) if nhwc else (co, ci, k_, k_)
            params[name] = jax.random.normal(sub, shape, jnp.bfloat16) * scale

        def mkbn(name, c):
            params[name] = (jnp.ones(c, jnp.float32),
                            jnp.zeros(c, jnp.float32))
        mk("stem", 7, 3, 64); mkbn("stem_bn", 64)
        cin = 64
        for si, (co, cm, n) in enumerate(stages):
            for bi in range(n):
                p = f"s{si}b{bi}"
                mk(p + "c1", 1, cin, cm)
                mk(p + "c2", 3, cm, cm)
                mk(p + "c3", 1, cm, co)
                mkbn(p + "bn1", cm); mkbn(p + "bn2", cm); mkbn(p + "bn3", co)
                if bi == 0:
                    mk(p + "sc", 1, cin, co); mkbn(p + "scbn", co)
                cin = co
        k[0], sub = jax.random.split(k[0])
        params["fc"] = jax.random.normal(sub, (head_c, 1000),
                                         jnp.bfloat16) * 0.01
        return params

    def block(x, params, p, stride, proj, training):
        y = bn(conv(x, params[p + "c1"]), params[p + "bn1"], training)
        y = jnp.maximum(y, 0)
        y = bn(conv(y, params[p + "c2"], stride), params[p + "bn2"], training)
        y = jnp.maximum(y, 0)
        y = bn(conv(y, params[p + "c3"]), params[p + "bn3"], training)
        if proj:
            x = bn(conv(x, params[p + "sc"], stride), params[p + "scbn"],
                   training)
        return jnp.maximum(x + y, 0)

    def block_fused(x, params, p, stride, proj, training):
        """Bottleneck with Pallas fused matmul+BN kernels on c1/c3/sc:
        1x1 convs emit their BN batch stats from the matmul epilogue and
        the c3 kernel applies bn2+relu in its prologue — no stats read
        passes, no materialized normalized copy of y2 (ops/fused_block)."""
        n, h, w_, _ = x.shape
        eps = 1e-5
        flat = lambda t: t.reshape(-1, t.shape[-1])
        sq = lambda w4: w4.reshape(w4.shape[2], w4.shape[3])  # 1x1 HWIO
        mrows = n * h * w_

        y1, a1, b1 = fb.fused_matmul_bn(flat(x), sq(params[p + "c1"]))
        g1, be1 = params[p + "bn1"]
        sc1, of1, _, _ = fb.bn_consts(a1, b1, mrows, g1, be1, eps)
        cm = y1.shape[-1]
        g2, be2 = params[p + "bn2"]
        if stride == 1:
            # round-5: the 3x3 goes through the conv-fused kernel too —
            # bn1+relu in the conv prologue (y1n never materialized),
            # bn2 stats from the conv epilogue (ops/fused_conv)
            from incubator_mxnet_tpu.ops.fused_conv import fused_conv3_bn
            y2, a2, b2 = fused_conv3_bn(y1.reshape(n, h, w_, cm),
                                        params[p + "c2"], sc1, of1)
            sc2, of2, _, _ = fb.bn_consts(a2, b2, mrows, g2, be2, eps)
        else:
            # stride-2 3x3 (this probe's stage transitions): XLA conv
            # with the materialized normalized copy — kernel is s1-only
            y1n = jnp.maximum(y1 * sc1.astype(x.dtype)
                              + of1.astype(x.dtype), 0)
            y1n = y1n.reshape(n, h, w_, cm)
            y2 = conv(y1n, params[p + "c2"], stride)
            mean2 = jnp.mean(y2, (0, 1, 2), dtype=jnp.float32)
            meansq2 = jnp.mean(jnp.square(y2), (0, 1, 2), dtype=jnp.float32)
            var2 = jnp.maximum(meansq2 - jnp.square(mean2), 0.0)
            rstd2 = lax.rsqrt(var2 + eps)
            sc2 = g2 * rstd2
            of2 = be2 - mean2 * sc2

        y3, a3, b3 = fb.fused_matmul_bn(flat(y2), sq(params[p + "c3"]),
                                        sc2, of2)
        g3, be3 = params[p + "bn3"]
        sc3, of3, _, _ = fb.bn_consts(a3, b3, y3.shape[0], g3, be3, eps)

        if proj:
            xs = x[:, ::stride, ::stride, :] if stride > 1 else x
            ysc, asc, bsc = fb.fused_matmul_bn(flat(xs), sq(params[p + "sc"]))
            gsc, besc = params[p + "scbn"]
            scc, ofc, _, _ = fb.bn_consts(asc, bsc, ysc.shape[0], gsc, besc,
                                          eps)
            short = ysc * scc.astype(x.dtype) + ofc.astype(x.dtype)
        else:
            short = flat(x)
        out = jnp.maximum(
            y3 * sc3.astype(x.dtype) + of3.astype(x.dtype) + short, 0)
        co = y3.shape[-1]
        return out.reshape(n, h // stride, w_ // stride, co)

    def make_loss(blk):
        def forward(params, x, training=True):
            y = conv(x, params["stem"], 2)
            y = jnp.maximum(bn(y, params["stem_bn"], training), 0)
            pool_w = (1, 3, 3, 1) if nhwc else (1, 1, 3, 3)
            pool_s = (1, 2, 2, 1) if nhwc else (1, 1, 2, 2)
            y = lax.reduce_window(y, -jnp.inf, lax.max, pool_w, pool_s,
                                  "SAME")
            for si, (co, cm, n) in enumerate(stages):
                for bi in range(n):
                    fn = (lambda yy, _si=si, _bi=bi, _n=n: blk(
                        yy, params, f"s{_si}b{_bi}",
                        (2 if _bi == 0 and _si > 0 else 1), _bi == 0,
                        training))
                    if remat:
                        fn = jax.checkpoint(fn)
                    y = fn(y)
            y = jnp.mean(y, (1, 2) if nhwc else (2, 3))
            return y.astype(jnp.bfloat16) @ params["fc"]

        def loss_fn(params, x, lbl):
            logits = forward(params, x).astype(jnp.float32)
            lp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(lp, lbl[:, None], 1))
        return loss_fn

    loss_fn = make_loss(block_fused if fused_blk else block)

    params = init()
    mom = jax.tree_util.tree_map(jnp.zeros_like, params)
    xshape = (bs, 224, 224, 3) if nhwc else (bs, 3, 224, 224)
    x = jax.random.normal(key, xshape, jnp.bfloat16)
    lbl = jax.random.randint(key, (bs,), 0, 1000)

    if fused_blk and os.environ.get("PROBE_VERIFY", "0") == "1":
        # Hardware cross-check: fused-kernel step vs pure-XLA step on
        # the SAME params/batch — catches a Mosaic miscompile in one
        # cheap extra compile instead of a silently-wrong benchmark.
        lv_f, g_f = jax.jit(jax.value_and_grad(make_loss(block_fused)))(
            params, x, lbl)
        lv_x, g_x = jax.jit(jax.value_and_grad(make_loss(block)))(
            params, x, lbl)
        rel = jax.tree_util.tree_map(
            lambda a, b: float(
                jnp.max(jnp.abs(a.astype(jnp.float32)
                                - b.astype(jnp.float32)))
                / (jnp.max(jnp.abs(b.astype(jnp.float32))) + 1e-6)),
            g_f, g_x)
        flat, _ = jax.tree_util.tree_flatten_with_path(rel)
        flat.sort(key=lambda kv: -kv[1])
        for path, v in flat[:5]:
            print(f"  grad reldiff {jax.tree_util.keystr(path)}: {v:.3e}",
                  flush=True)
        worst = flat[0][1]
        print(f"verify: loss fused={float(lv_f):.5f} xla={float(lv_x):.5f} "
              f"worst-grad-reldiff={worst:.3e}", flush=True)

    @jax.jit
    def step(params, mom, x, lbl):
        loss, g = jax.value_and_grad(loss_fn)(params, x, lbl)
        mom = jax.tree_util.tree_map(
            lambda m, gg: 0.9 * m + gg.astype(m.dtype), mom, g)
        params = jax.tree_util.tree_map(
            lambda p, m: p - (0.1 * m).astype(p.dtype), params, mom)
        return params, mom, x, lbl

    # analytic conv+fc FLOPs of THIS (possibly truncated) prefix so the
    # stages mode reports honest per-prefix MFU
    def prefix_flops():
        fl = 0.0

        def cf(k_, ci, co, hw):
            return 2.0 * k_ * k_ * ci * co * hw * hw
        fl += cf(7, 3, 64, 112)
        cin, hw = 64, 56
        for si, (co, cm, n) in enumerate(stages):
            for bi in range(n):
                stride = 2 if bi == 0 and si > 0 else 1
                # c1 runs PRE-stride (the stride lives in c2), so its
                # output is at the block's input resolution
                fl += cf(1, cin, cm, hw)
                hw_out = hw // stride
                fl += cf(3, cm, cm, hw_out) + cf(1, cm, co, hw_out)
                if bi == 0:
                    fl += cf(1, cin, co, hw_out)
                cin, hw = co, hw_out
        fl += 2.0 * head_c * 1000
        return 3 * fl * bs     # train ~ 3x forward

    flops = prefix_flops()
    dt = timeit(lambda p, m, a, b: step(p, m, a, b), (params, mom, x, lbl),
                steps=10, warmup=3)
    tag = (f"raw {layout} train bs={bs} remat={int(remat)} "
           f"bn={'batch' if bn_batch_stats else 'eval'}"
           + (" fusedblk" if fused_blk else "")
           + (f" stages<={len(stages)}" if max_stages is not None else ""))
    print(f"{tag}: {dt * 1e3:7.2f} ms  {bs / dt:7.1f} img/s  "
          f"{100 * flops / dt / PEAK:5.1f}% MFU  "
          f"({flops / 1e9:.0f} GFLOP)", flush=True)
    return dt


def probe_fmm():
    """Fused matmul+BN kernel microbenchmark vs the XLA composition, per
    characteristic ResNet-50 shape, plus a (BM, BN) block-size sweep —
    run on chip to tune ops/fused_block._pick_bm/_pick_bn (the sweep
    always includes the production heuristic's pick).  PROBE_BS
    scales M."""
    import functools
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import fused_block as fb

    bs = int(os.environ.get("PROBE_BS", "256"))
    # (label, HW, K, N, prologue) — stage2/stage4 c1 and c3 shapes
    shapes = [
        ("s1.c1 56px 256->64", 56 * 56, 256, 64, False),
        ("s1.c3 56px  64->256", 56 * 56, 64, 256, True),
        ("s3.c1 14px 1024->256", 14 * 14, 1024, 256, False),
        ("s3.c3 14px  256->1024", 14 * 14, 256, 1024, True),
        ("s4.c3  7px  512->2048", 7 * 7, 512, 2048, True),
    ]
    key = jax.random.PRNGKey(0)
    for label, hw, k, n, prologue in shapes:
        m = bs * hw
        kx, kw = jax.random.split(key)
        x = jax.random.normal(kx, (m, k), jnp.bfloat16) * 0.5
        w = jax.random.normal(kw, (k, n), jnp.bfloat16) * (k ** -0.5)
        sc = jnp.ones((k,), jnp.float32)
        bi = jnp.zeros((k,), jnp.float32)
        flops = 2.0 * m * k * n

        def time_fn(f):
            # carry-chained per the module timing discipline: step n+1's
            # x depends on step n's s1, so the final sync transitively
            # waits for every step (a 1-element donated update — no
            # extra activation traffic)
            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(x, w):
                _y, s1, _s2 = f(x, w)
                return x.at[0, 0].add((s1[0] * 1e-30).astype(x.dtype)), w
            # fresh buffer per config: step donates its x, and the next
            # config must not inherit a consumed input
            return timeit(step, (jnp.array(x), w), steps=10, warmup=2)

        dt_x = time_fn(lambda xx, ww: fb.xla_matmul_bn(
            xx, ww, sc if prologue else None, bi if prologue else None))
        best = None
        np_full = fb._round_up(n, 128)
        kp = fb._round_up(k, 128)
        for bm in (128, 256, 512):
            # narrow tiles, the whole width, and whatever production's
            # heuristic picks for this (kp, np_, bm) — no VMEM
            # pre-filter: a config that cannot compile reports FAIL
            bn_cands = sorted({b for b in (128, 256, 512, np_full,
                                           fb._pick_bn(kp, np_full, bm))
                               if np_full % b == 0})
            for bn in bn_cands:
                try:
                    dt = time_fn(functools.partial(
                        lambda xx, ww, _bm, _bn: fb._fwd_impl(
                            xx, ww, sc, bi, prologue, bm=_bm, bn=_bn),
                        _bm=bm, _bn=bn))
                except Exception as e:  # mxlint: allow-broad-except(probe harness: the failing config is printed and the sweep continues)
                    print(f"  {label} bm={bm} bn={bn}: FAIL "
                          f"{type(e).__name__}", flush=True)
                    continue
                if best is None or dt < best[0]:
                    best = (dt, bm, bn)
        if best is None:
            print(f"{label}: all block configs failed (xla "
                  f"{dt_x * 1e3:.3f} ms)", flush=True)
            continue
        dt_f, bm, bn = best
        print(f"{label}: xla {dt_x * 1e3:7.3f} ms ({flops / dt_x / 1e12:5.1f}"
              f" TF/s)  fused {dt_f * 1e3:7.3f} ms ({flops / dt_f / 1e12:5.1f}"
              f" TF/s) best bm={bm} bn={bn}  "
              f"{'WIN' if dt_f < dt_x else 'LOSS'} {dt_x / dt_f:5.2f}x",
              flush=True)


def probe_fc3():
    """Fused 3x3-conv+BN kernel A/B vs the XLA composition per ResNet
    stage-conv shape (PROBE_BS scales the batch) — run on chip to
    decide whether the conv kernel pays at each width
    (ops/fused_conv.py; the 512ch stage is expected to report its VMEM
    fallback)."""
    import functools
    import jax.numpy as jnp
    from incubator_mxnet_tpu.ops import fused_conv as fcv

    bs = int(os.environ.get("PROBE_BS", "256"))
    shapes = [("s1 56px  64ch", 56, 64), ("s2 28px 128ch", 28, 128),
              ("s3 14px 256ch", 14, 256), ("s4  7px 512ch", 7, 512)]
    key = jax.random.PRNGKey(0)
    for label, px, c in shapes:
        kx, kw = jax.random.split(jax.random.fold_in(key, c))
        x = jax.random.normal(kx, (bs, px, px, c), jnp.bfloat16) * 0.5
        w = jax.random.normal(kw, (3, 3, c, c), jnp.bfloat16) \
            * ((9 * c) ** -0.5)
        sc = jnp.ones((c,), jnp.float32)
        bi = jnp.zeros((c,), jnp.float32)
        flops = 2.0 * bs * px * px * 9 * c * c

        def time_fn(f):
            # carry-chained like probe_fmm: the final sync transitively
            # waits for every step
            @functools.partial(jax.jit, donate_argnums=(0,))
            def step(x, w):
                _y, s1, _s2 = f(x, w)
                return (x.at[0, 0, 0, 0].add(
                    (s1[0] * 1e-30).astype(x.dtype)), w)
            return timeit(step, (jnp.array(x), w), steps=10, warmup=2)

        dt_x = time_fn(lambda xx, ww: fcv.xla_conv3_bn(xx, ww, sc, bi))
        if not fcv._Geom(x, c).fits():
            print(f"{label}: xla {dt_x * 1e3:7.3f} ms "
                  f"({flops / dt_x / 1e12:5.1f} TF/s)  kernel: VMEM "
                  "fallback (by design)", flush=True)
            continue
        try:
            dt_f = time_fn(lambda xx, ww: fcv._fc3(xx, ww, sc, bi, True))
        except Exception as e:  # mxlint: allow-broad-except(probe harness: the failing kernel is printed and the sweep continues)
            print(f"{label}: xla {dt_x * 1e3:7.3f} ms  kernel FAIL "
                  f"{type(e).__name__}: {str(e)[:120]}", flush=True)
            continue
        print(f"{label}: xla {dt_x * 1e3:7.3f} ms ({flops / dt_x / 1e12:5.1f}"
              f" TF/s)  fused {dt_f * 1e3:7.3f} ms "
              f"({flops / dt_f / 1e12:5.1f} TF/s)  "
              f"{'WIN' if dt_f < dt_x else 'LOSS'} {dt_x / dt_f:5.2f}x",
              flush=True)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "fused"
    print(f"devices: {jax.devices()}", flush=True)
    print("MFU convention: multiply-add = 2 flops "
          f"(peak {PEAK / 1e12:.0f} TF/s bf16); every %-of-peak below "
          "uses it", flush=True)
    if mode == "matmul":
        probe_matmul()
    elif mode == "conv1":
        probe_conv1()
    elif mode == "ablate":
        probe_ablate()
    elif mode == "stem":
        probe_stem()
    elif mode == "layout":
        probe_layout()
    elif mode == "raw":
        probe_raw()
    elif mode == "fmm":
        probe_fmm()
    elif mode == "fc3":
        probe_fc3()
    elif mode == "stages":
        # prefix sweep: deltas between consecutive rows localize the
        # train-step time (fwd+bwd+opt) per ResNet stage
        times = [probe_raw(max_stages=k) for k in range(5)]
        for k in range(1, 5):
            d = (times[k] - times[k - 1]) * 1e3
            print(f"  stage{k} delta: {d:7.2f} ms", flush=True)
    else:
        probe_fused()
