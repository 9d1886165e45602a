"""Deploy/predict surface tests (VERDICT r2 task #9).

export_model → StableHLO + .params + meta artifacts; load_predictor
rebuilds the forward with no model code; the C ABI smoke binary
(src/predict.cc + predict_smoke.c) executes an exported model from C.
Reference: include/mxnet/c_predict_api.h.
"""
import os
import subprocess
import sys

import numpy as onp
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd, gluon, deploy

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE_BIN = os.path.join(REPO, "tools", "bin", "mxt_predict_smoke")


def _small_net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Conv2D(8, 3, padding=1, in_channels=3,
                            activation="relu"),
            gluon.nn.GlobalAvgPool2D(), gluon.nn.Flatten(),
            gluon.nn.Dense(4, in_units=8))
    net.initialize()
    return net


def test_export_artifacts_and_reload(tmp_path):
    net = _small_net()
    x = nd.random.uniform(shape=(2, 3, 16, 16))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    meta = deploy.export_model(net, (x,), prefix)
    for suffix in (".stablehlo.mlir", ".jaxport", ".params", ".meta.json"):
        assert os.path.exists(prefix + suffix), suffix
    assert meta["inputs"][0]["shape"] == [2, 3, 16, 16]
    # stablehlo text is real MLIR
    head = open(prefix + ".stablehlo.mlir").read(200)
    assert "module" in head and ("stablehlo" in head or "func" in head)
    pred = deploy.load_predictor(prefix)
    out = pred(x.asnumpy())
    onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_export_pure_function(tmp_path):
    import jax.numpy as jnp

    def fwd(params, x):
        return jnp.tanh(x @ params["w"]) + params["b"]

    params = {"w": jnp.ones((4, 3)), "b": jnp.zeros((3,))}
    x = onp.random.RandomState(0).rand(2, 4).astype(onp.float32)
    prefix = str(tmp_path / "fn")
    deploy.export_model(fwd, (x,), prefix, params=params)
    pred = deploy.load_predictor(prefix)
    onp.testing.assert_allclose(pred(x), onp.tanh(x @ onp.ones((4, 3))),
                                rtol=1e-5)


def test_c_predict_smoke(tmp_path):
    if not os.path.exists(SMOKE_BIN):
        proc = subprocess.run(["make", "-C", os.path.join(REPO, "src"),
                               "predict"], capture_output=True, text=True)
        if proc.returncode != 0 or not os.path.exists(SMOKE_BIN):
            pytest.skip(f"predict ABI build unavailable: {proc.stderr[-300:]}")
    net = _small_net()
    x = nd.random.uniform(shape=(2, 3, 16, 16))
    ref = net(x).asnumpy()
    prefix = str(tmp_path / "model")
    deploy.export_model(net, (x,), prefix)
    xin = x.asnumpy().astype(onp.float32)
    xin.tofile(prefix + ".smoke_in.bin")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([SMOKE_BIN, prefix, str(xin.size)],
                          capture_output=True, text=True, env=env,
                          timeout=300)
    assert proc.returncode == 0, (proc.stdout, proc.stderr[-500:])
    out = onp.fromfile(prefix + ".smoke_out.bin", onp.float32) \
        .reshape(ref.shape)
    onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_export_params_with_list_pytree(tmp_path):
    import jax.numpy as jnp

    def fwd(params, x):
        h = x @ params["layers"][0]
        return h @ params["layers"][1] + params["b"]

    params = {"layers": [jnp.ones((4, 5)), jnp.full((5, 2), 2.0)],
              "b": jnp.zeros((2,))}
    x = onp.random.RandomState(1).rand(3, 4).astype(onp.float32)
    prefix = str(tmp_path / "lst")
    deploy.export_model(fwd, (x,), prefix, params=params)
    pred = deploy.load_predictor(prefix)
    ref = (x @ onp.ones((4, 5))) @ onp.full((5, 2), 2.0)
    onp.testing.assert_allclose(pred(x), ref, rtol=1e-5)


def test_multithread_concurrency(tmp_path):
    """MXTPredCreateMultiThread (reference c_predict_api.h
    MXPredCreateMultiThread + cached_op_threadsafe role): N handles over
    one model, driven from N python threads through the C ABI via
    ctypes.  Asserts (a) correctness per thread, (b) the GIL is RELEASED
    during forward (a counter thread makes progress while another
    thread sits inside MXTPredForward), and (c) the N forwards overlap:
    a call starts while another is in flight."""
    import ctypes
    import threading
    import time

    lib_path = os.path.join(REPO, "incubator_mxnet_tpu", "native",
                            "libmxtpredict.so")
    if not os.path.exists(lib_path):
        proc = subprocess.run(["make", "-C", os.path.join(REPO, "src"),
                               "predict"], capture_output=True, text=True)
        if proc.returncode != 0 or not os.path.exists(lib_path):
            pytest.skip(f"predict ABI build unavailable: {proc.stderr[-300:]}")

    # compute-heavy pure fn so forward spends its time inside XLA
    import jax.numpy as jnp

    def fwd(params, x):
        y = x
        for _ in range(30):
            y = jnp.tanh(y @ params["w"])
        return y

    rng = onp.random.RandomState(0)
    params = {"w": rng.randn(256, 256).astype(onp.float32) * 0.05}
    x = rng.randn(8, 256).astype(onp.float32)
    prefix = str(tmp_path / "mt_model")
    deploy.export_model(fwd, (x,), prefix, params=params)
    ref = fwd(params, x)

    lib = ctypes.CDLL(lib_path)
    lib.MXTPredCreateMultiThread.argtypes = [
        ctypes.c_char_p, ctypes.c_uint32, ctypes.POINTER(ctypes.c_void_p)]
    # full argtypes: indexing a c_void_p array yields a bare int, which
    # ctypes would otherwise truncate to c_int (a 32-bit pointer crash)
    lib.MXTPredSetInput.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_float), ctypes.c_uint64]
    lib.MXTPredForward.argtypes = [ctypes.c_void_p]
    lib.MXTPredGetOutput.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32,
        ctypes.POINTER(ctypes.c_float), ctypes.c_uint64]
    lib.MXTPredFree.argtypes = [ctypes.c_void_p]
    NT = 4
    handles = (ctypes.c_void_p * NT)()
    assert lib.MXTPredCreateMultiThread(
        prefix.encode(), NT, handles) == 0
    size = x.size

    def forward(i, xin):
        buf = xin.ravel()
        assert lib.MXTPredSetInput(
            handles[i], 0,
            buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)), size) == 0
        assert lib.MXTPredForward(handles[i]) == 0
        out = onp.empty(ref.size, onp.float32)
        assert lib.MXTPredGetOutput(
            handles[i], 0,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            out.size) == 0
        return out.reshape(ref.shape)

    # (a) correctness: every handle computes the right answer for its
    # own input, concurrently
    inputs = [rng.randn(8, 256).astype(onp.float32) for _ in range(NT)]
    results = [None] * NT
    threads = [threading.Thread(
        target=lambda i=i: results.__setitem__(i, forward(i, inputs[i])))
        for i in range(NT)]
    forward(0, x)  # warm the executable (compile outside timing)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    for i in range(NT):
        onp.testing.assert_allclose(
            results[i], onp.asarray(fwd(params, inputs[i])),
            rtol=2e-4, atol=2e-5)

    # (b) GIL overlap: while thread A is inside MXTPredForward on a
    # genuinely slow model (shapes are static, so "heavy" means a
    # deeper artifact, not a bigger input), a pure python counter
    # thread must keep running
    def fwd_slow(params, x):
        y = x
        for _ in range(400):
            y = jnp.tanh(y @ params["w"])
        return y

    slow_prefix = str(tmp_path / "mt_model_slow")
    deploy.export_model(fwd_slow, (x,), slow_prefix, params=params)
    hslow = ctypes.c_void_p()
    lib.MXTPredCreate.argtypes = [ctypes.c_char_p,
                                  ctypes.POINTER(ctypes.c_void_p)]
    assert lib.MXTPredCreate(slow_prefix.encode(),
                             ctypes.byref(hslow)) == 0

    def forward_slow(xin):
        buf = xin.ravel()
        assert lib.MXTPredSetInput(
            hslow, 0, buf.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            buf.size) == 0
        assert lib.MXTPredForward(hslow) == 0

    ticks = []
    stop = threading.Event()

    def counter():
        while not stop.is_set():
            ticks.append(1)
            time.sleep(0.0005)

    forward_slow(x)   # compile outside the measurement
    t0 = time.perf_counter()
    forward_slow(x)   # one compiled forward's wall time
    fwd_time = time.perf_counter() - t0
    ct = threading.Thread(target=counter)
    ct.start()
    time.sleep(0.01)
    base = len(ticks)
    for _ in range(3):
        forward_slow(x)
    stop.set()
    ct.join()
    gained = len(ticks) - base
    # with the GIL held through forward, the counter would gain ~0;
    # demand it averaged at least ~100 ticks/sec through 3 forwards
    assert gained >= max(int(3 * fwd_time * 100), 3), \
        f"counter starved: {gained} ticks in {3 * fwd_time:.2f}s compute"
    lib.MXTPredFree(hslow)

    # (c) the NT forwards overlap, counted and not timed (a ratio of
    # wall times flakes on a loaded host): every thread keeps calling
    # its handle until some call has started while another was in
    # flight — ctypes drops the GIL at the ABI's boundary, the shim
    # takes it again, XLA drops it while it computes
    flight = {"now": 0, "most": 0}
    flight_lock = threading.Lock()
    deadline = time.monotonic() + 60

    def hammer(i):
        while flight["most"] < 2 and time.monotonic() < deadline:
            with flight_lock:
                flight["now"] += 1
                flight["most"] = max(flight["most"], flight["now"])
            try:
                forward(i, inputs[i])
            finally:
                with flight_lock:
                    flight["now"] -= 1

    threads = [threading.Thread(target=hammer, args=(i,))
               for i in range(NT)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert flight["most"] >= 2, \
        f"{NT} threads never had two forwards in flight: {flight}"

    for i in range(NT):
        lib.MXTPredFree(handles[i])


# ---------------------------------------------------------------------------
# batched predictor surface (ISSUE 3: the dynamic batcher's substrate)
# ---------------------------------------------------------------------------

def _export_tanh_mlp(tmp_path, name="bm"):
    import jax.numpy as jnp

    def fwd(params, x):
        return jnp.tanh(x @ params["w"]) + params["b"]

    rng = onp.random.RandomState(3)
    params = {"w": rng.randn(12, 5).astype(onp.float32),
              "b": rng.randn(5).astype(onp.float32)}
    x = rng.randn(2, 12).astype(onp.float32)
    prefix = str(tmp_path / name)
    meta = deploy.export_model(fwd, (x,), prefix, params=params)
    return prefix, params, meta


def test_predictor_accepts_batched_leading_dims(tmp_path):
    """load_predictor serves any leading batch dim via the shape-
    polymorphic twin export, matching the traced-shape result rows."""
    prefix, params, meta = _export_tanh_mlp(tmp_path)
    assert meta["batch_export"] is True
    assert os.path.exists(prefix + ".batch.jaxport")
    pred = deploy.load_predictor(prefix)
    assert pred.batch_polymorphic
    rng = onp.random.RandomState(5)
    xb = rng.randn(16, 12).astype(onp.float32)
    ref = onp.tanh(xb @ params["w"]) + params["b"]
    for n in (1, 3, 8, 16):
        out = pred(xb[:n])
        assert out.shape == (n, 5)
        onp.testing.assert_allclose(out, ref[:n], rtol=1e-5, atol=1e-6)
    # per-row results identical regardless of the batch they rode in
    assert (pred(xb[:1])[0] == pred(xb[:7])[0]).all()


def test_predictor_batched_input_validation(tmp_path):
    prefix, _, _ = _export_tanh_mlp(tmp_path)
    pred = deploy.load_predictor(prefix)
    with pytest.raises(ValueError, match="exported signature"):
        pred(onp.zeros((4, 9), onp.float32))     # wrong trailing dim
    with pytest.raises(ValueError, match="exported signature"):
        pred(onp.zeros((4, 12, 1), onp.float32))  # wrong rank


def test_predictor_warm_shapes_do_not_recompile(tmp_path):
    """Regression for the batcher's core dependency: calls at an
    already-seen batch size must not re-trace/re-compile (the
    compile-count probe reads the jit executable caches)."""
    prefix, _, _ = _export_tanh_mlp(tmp_path)
    pred = deploy.load_predictor(prefix)
    warmed = pred.warmup([1, 2, 4, 8])
    assert warmed == pred.compile_count
    rng = onp.random.RandomState(1)
    for n in (1, 2, 4, 8, 8, 4, 2, 1):
        pred(rng.randn(n, 12).astype(onp.float32))
    assert pred.compile_count == warmed, \
        "warm-shape call re-traced the executable"
    # a genuinely new shape is allowed to compile exactly once more
    pred(rng.randn(5, 12).astype(onp.float32))
    assert pred.compile_count == warmed + 1
    pred(rng.randn(5, 12).astype(onp.float32))
    assert pred.compile_count == warmed + 1


def test_predictor_chunked_fallback_without_batch_export(tmp_path):
    """Artifacts without the polymorphic twin (older exports, or models
    that constrain the batch dim) still serve any batch size by
    chunking/padding to the traced batch size."""
    import json as _json
    prefix, params, _ = _export_tanh_mlp(tmp_path)
    os.remove(prefix + ".batch.jaxport")
    with open(prefix + ".meta.json") as f:
        meta = _json.load(f)
    meta["batch_export"] = False
    with open(prefix + ".meta.json", "w") as f:
        _json.dump(meta, f)
    pred = deploy.load_predictor(prefix)
    assert not pred.batch_polymorphic
    rng = onp.random.RandomState(8)
    for n in (1, 2, 3, 5, 7):   # traced batch is 2: exercises padding
        xb = rng.randn(n, 12).astype(onp.float32)
        ref = onp.tanh(xb @ params["w"]) + params["b"]
        out = pred(xb)
        assert out.shape == (n, 5)
        onp.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# PJRT-direct predictor (src/pjrt_predict.cc): the NO-python serving
# path (VERDICT r3 Next #8 option A)
# ---------------------------------------------------------------------------

PJRT_SMOKE = os.path.join(REPO, "tools", "bin", "mxt_pjrt_smoke")


def _build_pjrt():
    if not os.path.exists(PJRT_SMOKE):
        proc = subprocess.run(["make", "-C", os.path.join(REPO, "src"),
                               "pjrt"], capture_output=True, text=True)
        if proc.returncode != 0 or not os.path.exists(PJRT_SMOKE):
            pytest.skip(f"pjrt build unavailable: {proc.stderr[-300:]}")


def test_pjrt_predictor_loud_on_bad_plugin(tmp_path):
    """The ABI fails with a clear dlopen error, not a crash — exercised
    without any accelerator."""
    _build_pjrt()
    proc = subprocess.run(
        [PJRT_SMOKE, "/nonexistent/plugin.so", "", str(tmp_path / "m")],
        capture_output=True, text=True, timeout=60)
    assert proc.returncode == 1
    assert "dlopen" in proc.stderr and "plugin.so" in proc.stderr


def test_pjrt_sidecar_artifacts_written(tmp_path):
    """deploy.export_model writes the manifest + raw params the C
    runtime parses; verify offsets and the line format."""
    import jax.numpy as jnp

    def fwd(params, x):
        return x @ params["w"] + params["b"]

    params = {"w": onp.arange(12, dtype=onp.float32).reshape(3, 4),
              "b": onp.ones(4, onp.float32)}
    x = onp.zeros((2, 3), onp.float32)
    prefix = str(tmp_path / "m")
    deploy.export_model(fwd, (x,), prefix, params=params)
    raw = open(prefix + ".pjrt_params.bin", "rb").read()
    lines = open(prefix + ".pjrt.txt").read().splitlines()
    args = [l.split() for l in lines if l.startswith("arg ")]
    outs = [l.split() for l in lines if l.startswith("out ")]
    assert [a[1] for a in args] == ["param", "param", "input"]
    # params are raw little-endian at the recorded offsets, in
    # tree-flatten (alphabetical dict) order: b then w
    b_off, b_nb = int(args[0][3]), int(args[0][4])
    onp.testing.assert_array_equal(
        onp.frombuffer(raw[b_off:b_off + b_nb], onp.float32),
        params["b"])
    w_off, w_nb = int(args[1][3]), int(args[1][4])
    onp.testing.assert_array_equal(
        onp.frombuffer(raw[w_off:w_off + w_nb], onp.float32),
        params["w"].ravel())
    assert outs[0][1] == "float32" and outs[0][2:] == ["2", "2", "4"]
    assert os.path.getsize(prefix + ".compile_options.pb") > 0
