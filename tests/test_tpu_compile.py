"""The main path's Pallas kernels compile for the chip, at real widths.

This sandbox has no TPU, but the TPU's compiler is installed and compiles
for a chip that is *described* and not attached (the `on-chip-measurement`
guide, section 2, third rehearsal).  Interpret mode cannot see what these
tests see: a block shape Mosaic's tiling refuses (the LayerNorm/RMSNorm
backward's (1, cols) partials), more scoped VMEM than a kernel may use (the
fused conv at ResNet-50 stage 1, every row-wise kernel at its widest rows),
a kernel GSPMD is asked to partition.  Each test asserts a
``tpu_custom_call`` in the compiled program.  Nothing runs, so nothing here
says a result is right or fast — ``chip_smoke.py``'s ``kernels`` phase does
that on the chip.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU's library,
every xdist worker imports every test file, and only the worker that is
given this file may load it.  All such tests stay in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as onp
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from incubator_mxnet_tpu import executor_cache as xc
from incubator_mxnet_tpu.ops import fused_block, fused_conv
from incubator_mxnet_tpu.ops import pallas_kernels as pk

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # whatever keeps the compiler from loading here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def for_the_chip(monkeypatch):
    """Steer the kernels' own platform test: `interpret_mode()` and
    `dispatch()` ask `jax.default_backend()`, which is the CPU here."""
    monkeypatch.delenv("MXNET_USE_PALLAS", raising=False)
    monkeypatch.setattr(pk.jax, "default_backend", lambda: "tpu")


def _compile(fn, *specs):
    # a described-topology compile cannot be read back from the persistent
    # cache without a chip; keep it out of the way
    with xc.compile_cache_bypassed():
        return jax.jit(fn).lower(*specs).compile().as_text()


def _fwd_bwd(fn, n_diff):
    """fn's forward and backward as one program (unit cotangents)."""
    def run(*a):
        out, vjp = jax.vjp(lambda *d: fn(*d, *a[n_diff:]), *a[:n_diff])
        return out, vjp(jax.tree_util.tree_map(jnp.ones_like, out))
    return run


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (rows, cols, dtype): BERT-base activations, a 1024-wide f32 stack, and
# the widest row the kernels take (_MAX_COLS) — each more than one row block
ROWWISE = [(16384, 768, BF16), (8192, 1024, F32), (4096, 16384, F32)]


@pytest.mark.parametrize("rows,cols,dtype", ROWWISE)
def test_layer_norm_fwd_bwd(one_chip, for_the_chip, rows, cols, dtype):
    s = functools.partial(_spec, one_chip)
    text = _compile(_fwd_bwd(pk.fused_layer_norm, 3),
                    s((rows, cols), dtype), s((cols,), F32), s((cols,), F32))
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("rows,cols,dtype", ROWWISE)
def test_rms_norm_fwd_bwd(one_chip, for_the_chip, rows, cols, dtype):
    s = functools.partial(_spec, one_chip)
    text = _compile(_fwd_bwd(pk.fused_rms_norm, 2),
                    s((rows, cols), dtype), s((cols,), F32))
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("shape,dtype", [
    ((8, 12, 512, 512), BF16),      # BERT-base attention probabilities
    ((4096, 16384), F32)])
def test_softmax_fwd_bwd(one_chip, for_the_chip, shape, dtype):
    text = _compile(_fwd_bwd(pk.fused_softmax, 1),
                    _spec(one_chip, shape, dtype))
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("rows,cols,dtype", [
    (256, 1000, F32),               # ResNet-50 logits at batch 256
    (4096, 16384, BF16)])
def test_softmax_xent_fwd_bwd(one_chip, for_the_chip, rows, cols, dtype):
    s = functools.partial(_spec, one_chip)
    text = _compile(_fwd_bwd(pk.fused_softmax_xent, 1),
                    s((rows, cols), dtype), s((rows,), I32))
    assert text.count("tpu_custom_call") == 2


# BERT-base's attention in the benchmark's cell, the float32 reference
# step's (batch 2), long causal sequences (several blocks each way: a
# grid over the live pairs, their tables prefetched into SMEM; at 8,192
# four heads a step both ways: a head's whole dq, float32 sum and two
# output buffers, is 4 MiB of the backward's bill), and the longest
# causal length at width 64 that compiled before the backward had a bill
# of its own (60,416: 118 x 118 blocks, 7,021 live pairs, two heads a
# step under the 64 MiB the plan may ask for; 60,928 did not compile
# under 32 MiB), so that no change to the bill shrinks it unseen
@pytest.mark.parametrize("shape,dtype,causal,heads", [
    ((32, 12, 512, 64), BF16, False, (4, 4)),
    ((2, 12, 512, 64), F32, False, (4, 4)),
    ((8, 16, 2048, 64), BF16, True, (4, 4)),
    ((1, 8, 8192, 64), BF16, True, (4, 4)),
    ((1, 8, 60416, 64), BF16, True, (4, 2))])
def test_flash_attention_fwd_bwd(one_chip, for_the_chip, shape, dtype,
                                 causal, heads):
    from incubator_mxnet_tpu.ops import nn_ops
    q = _spec(one_chip, shape, dtype)
    attend = functools.partial(nn_ops.dot_product_attention.fn, causal=causal)
    pk.attention_plans(reset=True)
    text = _compile(_fwd_bwd(attend, 3), q, q, q)
    assert text.count("tpu_custom_call") == 2
    # neither pass holds a (T, S) tensor outside the kernels
    assert f"{shape[2]},{shape[2]}]" not in text
    (plan,) = pk.attention_plans().values()
    assert (plan["heads_fwd"], plan["heads_bwd"]) == heads


def test_flash_attention_with_a_narrower_v(one_chip, for_the_chip):
    """Latent attention at the published widths and the benchmark's
    length: 192 wide in q and k (128 + 64 rotary), 128 in v, causal, 4,096
    keys (8 x 8 blocks of which 36 are live, four heads a step both ways;
    the backward holds four heads' whole dq, 192 x 4,096 each, in VMEM
    and asks Mosaic for the 38 MiB that takes)."""
    from incubator_mxnet_tpu.ops import nn_ops
    qk = _spec(one_chip, (2, 32, 4096, 192), BF16)
    v = _spec(one_chip, (2, 32, 4096, 128), BF16)
    attend = functools.partial(nn_ops.dot_product_attention.fn, causal=True)
    pk.attention_plans(reset=True)
    text = _compile(_fwd_bwd(attend, 3), qk, qk, v)
    assert text.count("tpu_custom_call") == 2
    assert "4096,4096]" not in text
    assert pk.attention_plans() == {
        "bh64 d192/128 t4096x4096 causal bfloat16": {
            "heads_fwd": 4, "heads_bwd": 4, "grid_steps_fwd": 576,
            "grid_steps_bwd": 576, "pairs": 64, "live_pairs": 36,
            "diagonal_pairs": 8}}
    # no operand is padded to another's width: nothing 192 wide but q, k
    # and their gradients reaches a kernel
    assert "bf16[64,128,4096]" in text and "bf16[64,192,4096]" in text


def test_moe_ffn_fwd_bwd(one_chip, for_the_chip):
    """The routed layer at the published widths and the benchmark's
    traffic: 8,192 tokens, top-8 over 256 experts of which 16 are held,
    hidden 2,048, expert width 768: two forward grouped matmuls, and in
    the backward pass the first again, two for the rows' gradients and two
    for the weights'."""
    from incubator_mxnet_tpu.ops import moe_ops
    s = functools.partial(_spec, one_chip)
    ffn = functools.partial(moe_ops.moe_ffn.fn, n_experts=256, first=0,
                            capacity_factor=1.5)

    def run(x, gates, w_in, w_out, idx):
        return _fwd_bwd(lambda *d: ffn(d[0], idx, *d[1:])[0], 4)(
            x, gates, w_in, w_out)

    text = _compile(run, s((8192, 2048), BF16), s((8192, 8), F32),
                    s((16, 2048, 1536), BF16), s((16, 768, 2048), BF16),
                    s((8192, 8), I32))
    # seven in the pass every step takes, seven in the body of the loop
    # that runs only when rows outgrow the buffer
    assert text.count("tpu_custom_call") == 14
    # no (tokens, experts, capacity) one-hot tensor
    assert "8192,16," not in text and "8192,256," not in text


# ResNet-50's four stages at batch 256: (pixels a side, mid width, out width)
STAGES = [(56, 64, 256), (28, 128, 512), (14, 256, 1024), (7, 512, 2048)]


@pytest.mark.parametrize("hw,cm,co", [STAGES[0], STAGES[3]])
def test_fused_matmul_bn_fwd_bwd(one_chip, for_the_chip, hw, cm, co):
    s = functools.partial(_spec, one_chip)
    m = 256 * hw * hw
    text = _compile(_fwd_bwd(fused_block.fused_matmul_bn, 4),
                    s((m, cm), BF16), s((cm, co), BF16),
                    s((cm,), F32), s((cm,), F32))
    assert text.count("tpu_custom_call") == 3       # fwd, dx, dw


@pytest.mark.parametrize("hw,cm,co", STAGES)
def test_fused_conv3_bn_fwd_bwd(one_chip, for_the_chip, hw, cm, co):
    x = jax.ShapeDtypeStruct((256, hw, hw, cm), BF16)
    plan = fused_conv._Geom(x, cm)
    assert plan.fits() and plan.n_blocks == 1
    s = functools.partial(_spec, one_chip)
    text = _compile(_fwd_bwd(fused_conv.fused_conv3_bn, 4),
                    s(x.shape, BF16), s((3, 3, cm, cm), BF16),
                    s((cm,), F32), s((cm,), F32))
    assert text.count("tpu_custom_call") == 3       # fwd, dx, dw


def test_fused_conv3_bn_several_output_blocks(one_chip, for_the_chip,
                                              monkeypatch):
    """The multi-N-block plan (outputs too wide for one block) at stage-4
    width: a budget below the one-block estimate forces two blocks."""
    monkeypatch.setattr(fused_conv, "_VMEM_BUDGET", 40 * 2 ** 20)
    x = jax.ShapeDtypeStruct((256, 7, 7, 512), BF16)
    assert fused_conv._Geom(x, 512).n_blocks == 2
    s = functools.partial(_spec, one_chip)
    text = _compile(_fwd_bwd(fused_conv.fused_conv3_bn, 4),
                    s(x.shape, BF16), s((3, 3, 512, 512), BF16),
                    s((512,), F32), s((512,), F32))
    assert text.count("tpu_custom_call") == 3


def test_fused_conv3_bn_image_block_beyond_vmem_rides_xla(one_chip,
                                                          for_the_chip):
    """A 112x112x64 image block needs ~82 MiB of scoped VMEM (bisected):
    the plan must say it does not fit, and the op says so and runs the XLA
    composition instead of handing Mosaic a kernel it refuses."""
    x = jax.ShapeDtypeStruct((8, 112, 112, 64), BF16)
    assert not fused_conv._Geom(x, 64).fits()
    s = functools.partial(_spec, one_chip)
    with pytest.warns(UserWarning, match="runs the XLA composition"):
        text = _compile(fused_conv.fused_conv3_bn,
                        s(x.shape, BF16), s((3, 3, 64, 64), BF16),
                        s((64,), F32), s((64,), F32))
    assert "tpu_custom_call" not in text


def test_dropout_mask_is_drawn_once_and_kept(one_chip):
    """BERT's `dropout(x . w) + res`, forward and backward, at the
    benchmark's widths: the mask's generator is ONE instruction of the
    compiled program (threefry rounds were fused into all three of the
    forward matmul and the two gradient matmuls: PERF.md §6, PR 33), the
    mask is a `pred` array, and the backward pass's matmul fusions take
    it as an operand instead of drawing it again."""
    import re
    from incubator_mxnet_tpu.ops import nn_ops
    rows = _spec(one_chip, (16384, 768), BF16)
    w = _spec(one_chip, (768, 768), BF16)
    key = _spec(one_chip, (2,), jnp.uint32)

    def proj(x, w, res, key):
        return nn_ops.dropout.fn(jnp.dot(x, w), key, p=0.1) + res

    text = _compile(_fwd_bwd(proj, 3), rows, w, rows, key)
    assert len(re.findall(r" rng-bit-generator\(", text)) == 1
    # nothing left of a counter-based generator's rounds over the rows
    assert not [c for c in text.split("\n\n") if "shift-right-logical" in c
                and "16384" in c.splitlines()[0]]
    entry = text[text.index("\nENTRY "):]
    (mask,) = re.findall(r"(%[\w.\-]+) = pred\[16384,768\]", entry)
    readers = [line for line in entry.splitlines() if " fusion(" in line
               and mask in re.findall(
                   r"%[\w.\-]+", line.split(" fusion(")[1].split(")")[0])]
    backward = [line for line in readers if "transpose(jvp" in line]
    assert len(readers) == 3 and len(backward) == 2
    assert nn_ops.dropout_masks()["16384x768 p0.1 bfloat16"] == {
        "elements": 16384 * 768, "generator": "rng_bit_generator",
        "kept_bytes": 16384 * 768}


# the two published shapes of the scan: Falcon-H1's 32 heads of 128 over a
# 256-wide state in 2 groups, Nemotron-H's 128 heads of 64 over a 128-wide
# state in 8 groups; 16 heads a group in both
@pytest.mark.parametrize("heads,p,groups,n", [(32, 128, 2, 256),
                                              (128, 64, 8, 128)])
def test_ssd_scan_fwd_bwd(one_chip, for_the_chip, heads, p, groups, n):
    """The chunked state-space scan at a published shape and the benchmark's
    length (4,096 positions, 32 chunks of 128), forward and backward, a
    group's 16 heads a grid step.  Nemotron-H's shape is the Mosaic calls
    ``ssd_scan_fwd`` and ``ssd_scan_bwd`` with the 32 float32 entry states a
    sequence between them — no decay or score matrix of a chunk in HBM, in
    either dtype.  Falcon-H1's, whose group's state is 2 MiB, is
    ``ssd_scan_fwd`` and the composition's backward from the same entry
    states (``ops/ssm_ops.py``, ``_SCAN_BWD_STATE_MOST``).  Neither has a
    (T, T) tensor."""
    import re
    from incubator_mxnet_tpu.ops import ssm_ops
    s = functools.partial(_spec, one_chip)
    ssm_ops.ssm_plans(reset=True)
    pk.kernel_routes(reset=True)
    text = _compile(
        _fwd_bwd(functools.partial(ssm_ops.ssd_scan.fn, chunk=128), 6),
        s((1, 4096, heads, p), BF16), s((1, 4096, heads), F32),
        s((heads,), F32), s((1, 4096, groups, n), BF16),
        s((1, 4096, groups, n), BF16), s((heads,), F32))
    pair = heads == 128
    assert text.count("tpu_custom_call") == (2 if pair else 1)
    assert "ssd_scan_fwd" in text and ("ssd_scan_bwd" in text) == pair
    # nothing (T, T) but Falcon-H1's x itself, whose heads are 4,096 wide
    assert set(re.findall(r"\[[\d,]*4096,4096\]", text)) <= {"[1,4096,4096]"}
    assert bool(re.search(r"(f32|bf16)\[[\d,]*128,128\]", text)) != pair
    assert f"f32[1,32,{heads * p},{n}]" in text     # the entry states
    assert pk.kernel_routes()["ssd_scan"] == {"kernel": 1}
    (plan,) = ssm_ops.ssm_plans().values()
    assert list(ssm_ops.ssm_plans()) == [
        f"b1 t4096 h{heads}x{p} g{groups} n{n} bfloat16"]
    assert 8 * 2 ** 20 < plan.pop("vmem_bytes") < 32 * 2 ** 20
    assert plan == {
        "chunk": 128, "chunks": 32, "heads_a_step": 16, "route": "kernel",
        "state_bytes_saved": 4 * 32 * heads * p * n, "padded_rows": 0,
        "grid_steps_fwd": 32 * groups,
        "grid_steps_bwd": 32 * groups if pair else 0}


# the two published widths of the mixer's convolution: Nemotron-H's 8,192 +
# 2 x 8 x 128 channels, Falcon-H1's 4,096 + 2 x 2 x 256
@pytest.mark.parametrize("channels", [10240, 5120])
def test_causal_conv1d_fwd_bwd(one_chip, for_the_chip, channels):
    """The mixer's short convolution at a published width and the
    benchmark's length (4,096 positions, 4 taps, bfloat16), forward and
    backward: exactly the Mosaic calls ``causal_conv1d_fwd`` and
    ``causal_conv1d_bwd``, a lane tile of channels a grid step, and no
    float32 tensor of the sequence's size in HBM — neither the
    composition's padded input nor a sum before SiLU kept for the
    backward."""
    import re
    from incubator_mxnet_tpu.ops import ssm_ops
    s = functools.partial(_spec, one_chip)
    ssm_ops.ssm_plans(reset=True)
    pk.kernel_routes(reset=True)
    text = _compile(_fwd_bwd(ssm_ops.causal_conv1d.fn, 3),
                    s((1, 4096, channels), BF16), s((channels, 4), BF16),
                    s((channels,), BF16))
    assert text.count("tpu_custom_call") == 2
    assert "causal_conv1d_fwd" in text and "causal_conv1d_bwd" in text
    assert not re.search(r"f32\[1,40\d\d,%d\]" % channels, text)
    assert pk.kernel_routes()["causal_conv1d"] == {"kernel": 1}
    (plan,) = ssm_ops.ssm_plans().values()
    assert list(ssm_ops.ssm_plans()) == [
        f"conv b1 t4096 c{channels} k4 bfloat16"]
    # the bill is under the limit the calls ask for
    assert 8 * 2 ** 20 < plan.pop("vmem_bytes") <= ssm_ops._CONV_VMEM
    assert plan == {
        "route": "kernel", "taps": 4, "channels_a_step": 128,
        "padded_rows": 0, "grid_steps_fwd": channels // 128,
        "grid_steps_bwd": channels // 128}


def test_flash_attention_with_fewer_key_heads(one_chip, for_the_chip):
    """Grouped-query attention at the published widths and the benchmark's
    length: 20 query heads over 4 key heads, 128 wide, causal, 4,096 keys.
    The key heads are repeated to 20 outside the kernels, which then run
    PR 31's live-pair grid (8 x 8 blocks, 36 live)."""
    from incubator_mxnet_tpu.ops import nn_ops
    q = _spec(one_chip, (1, 20, 4096, 128), BF16)
    kv = _spec(one_chip, (1, 4, 4096, 128), BF16)
    attend = functools.partial(nn_ops.dot_product_attention.fn, causal=True)
    pk.attention_plans(reset=True)
    text = _compile(_fwd_bwd(attend, 3), q, kv, kv)
    assert text.count("tpu_custom_call") == 2
    assert "4096,4096]" not in text
    (plan,) = pk.attention_plans().values()
    assert list(pk.attention_plans()) == [
        "bh20 d128/128 t4096x4096 causal bfloat16"]
    assert (plan["pairs"], plan["live_pairs"], plan["diagonal_pairs"]) == \
        (64, 36, 8)


def test_the_falcon_h1_step_fits_the_chip_with_its_blocks_recomputed(
        one_chip, for_the_chip):
    """The whole fused train step of ``falcon_h1_34b`` at the cell's size (1
    x 4,096 tokens, four whole blocks at the published widths, 2.05 B
    parameters with their Adam moments) plans under the chip's memory with
    half a GiB to spare — from shapes alone (``jax.eval_shape`` over the
    configuration's ``build`` and ``make_fused_train_step``: nothing is
    allocated) — and ``ssm_plans`` holds the step's two signatures."""
    import json
    import os
    import sys
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.ops import ssm_ops
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from chipbench.configs import falcon_h1_34b as model
    with open(os.path.join(repo, "chipbench", "configs",
                           "falcon_h1_34b.json")) as f:
        config = json.load(f)
    held = {}

    def make():
        built = model.build(0, config)
        amp.convert_block(built["net"], config["dtype"])
        step = make_fused_train_step(
            built["net"], built["loss"], built["optimizer"],
            dict(built["optimizer_params"]))
        held["step"] = step.step_fn
        return step.params, step.aux, step.opt_state, step._key

    *state, key = jax.eval_shape(make)
    on_chip = lambda v: _spec(one_chip, v.shape, v.dtype)
    ssm_ops.ssm_plans(reset=True)
    with xc.compile_cache_bypassed():
        compiled = jax.jit(held["step"], donate_argnums=(0, 1, 2)).lower(
            *jax.tree_util.tree_map(on_chip, state),
            _spec(one_chip, (1, 4096), I32),
            _spec(one_chip, (1, 1, 4096), I32), on_chip(key)).compile()
    plan = compiled.memory_analysis()
    assert sum(v.size for v in state[0].values()) == 2_054_718_848
    assert plan.argument_size_in_bytes > 11.4 * 2 ** 30
    assert plan.alias_size_in_bytes > 11.4 * 2 ** 30       # donated
    peak = (plan.argument_size_in_bytes + plan.output_size_in_bytes
            - plan.alias_size_in_bytes + plan.temp_size_in_bytes)
    # what memory_stats()["bytes_limit"] reads on the chip (PR 34)
    assert peak < (15.748 - 0.5) * 2 ** 30, peak / 2 ** 30
    text = compiled.as_text()
    assert "rematted_computation" in text
    # 4 blocks' attention forward, again and backward, and the norms
    assert text.count("flash_attention_fwd") >= 8
    # and their scans, a group's 16 heads a step of the forward kernel
    # (this shape's backward is the composition's: _SCAN_BWD_STATE_MOST)
    assert text.count("ssd_scan_fwd") >= 8 and "ssd_scan_bwd" not in text
    plan, conv = ssm_ops.ssm_plans().values()
    assert list(ssm_ops.ssm_plans()) == [
        "b1 t4096 h32x128 g2 n256 bfloat16", "conv b1 t4096 c5120 k4 bfloat16"]
    # the convolutions are the pair's, a lane tile of channels a step
    assert text.count("causal_conv1d_fwd") >= 8
    assert text.count("causal_conv1d_bwd") >= 4
    assert (conv["route"], conv["grid_steps_bwd"]) == ("kernel", 40)
    assert plan == {
        "chunk": 128, "chunks": 32, "heads_a_step": 16, "route": "kernel",
        "state_bytes_saved": 4 * 32 * 32 * 128 * 256, "padded_rows": 0,
        "grid_steps_fwd": 64, "grid_steps_bwd": 0,
        "vmem_bytes": plan["vmem_bytes"]}


def test_moe_ffn_ungated_in_a_latent_fwd_bwd(one_chip, for_the_chip):
    """The routed layer's held experts at the other configuration's widths
    and traffic: 4,096 tokens, top-22 over 512 experts of which 16 are held,
    in a 1,024-wide latent, ungated ``relu²`` experts 2,688 wide: the same
    seven grouped matmuls each way, a buffer of 7,040 rows."""
    from incubator_mxnet_tpu.ops import moe_ops
    s = functools.partial(_spec, one_chip)
    ffn = functools.partial(moe_ops.moe_ffn.fn, n_experts=512, first=0,
                            capacity_factor=1.75, activation="relu2")

    def run(x, gates, w_in, w_out, idx):
        return _fwd_bwd(lambda *d: ffn(d[0], idx, *d[1:])[0], 4)(
            x, gates, w_in, w_out)

    moe_ops.moe_plans(reset=True)
    text = _compile(run, s((4096, 1024), BF16), s((4096, 22), F32),
                    s((16, 1024, 2688), BF16), s((16, 2688, 1024), BF16),
                    s((4096, 22), I32))
    assert text.count("tpu_custom_call") == 14
    assert "4096,16," not in text and "4096,512," not in text
    assert moe_ops.moe_plans() == {
        "t4096 k22 e16/512 w1024 i2688 relu2 bfloat16": {
            "tokens": 4096, "top_k": 22, "n_experts": 512, "held": 16,
            "assignments": 90112, "buffer_rows": 7040, "tile": 128,
            "width": 1024, "hidden": 2688, "activation": "relu2"}}


def test_the_nemotron_h_step_fits_the_chip_with_its_layers_recomputed(
        one_chip, for_the_chip):
    """The whole fused train step of ``nemotron3_super_120b`` at the cell's
    size (1 x 4,097 tokens, one pattern period of 11 layers and the MTP
    module at the published widths, 1.64 B parameters with their Adam
    moments, every layer recomputed, the routed ones with their state) plans
    under the chip's memory with room to spare — from shapes alone
    (``jax.eval_shape`` over the configuration's ``build`` and
    ``make_fused_train_step``: nothing is allocated) — and ``moe_plans`` and
    ``ssm_plans`` hold the step's three signatures."""
    import json
    import os
    import sys
    from incubator_mxnet_tpu import amp
    from incubator_mxnet_tpu.fuse import make_fused_train_step
    from incubator_mxnet_tpu.ops import moe_ops, ssm_ops
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, repo)
    from chipbench.configs import nemotron3_super_120b as model
    with open(os.path.join(repo, "chipbench", "configs",
                           "nemotron3_super_120b.json")) as f:
        config = json.load(f)
    held = {}

    def make():
        built = model.build(0, config)
        amp.convert_block(built["net"], config["dtype"])
        step = make_fused_train_step(
            built["net"], built["loss"], built["optimizer"],
            dict(built["optimizer_params"]))
        held["step"] = step.step_fn
        return step.params, step.aux, step.opt_state, step._key

    *state, key = jax.eval_shape(make)
    on_chip = lambda v: _spec(one_chip, v.shape, v.dtype)
    moe_ops.moe_plans(reset=True)
    ssm_ops.ssm_plans(reset=True)
    with xc.compile_cache_bypassed():
        compiled = jax.jit(held["step"], donate_argnums=(0, 1, 2)).lower(
            *jax.tree_util.tree_map(on_chip, state),
            _spec(one_chip, (1, 4097), I32),
            _spec(one_chip, (1, 2, 4096), I32), on_chip(key)).compile()
    plan = compiled.memory_analysis()
    # the formula's 1,642,965,888 less the six routers' 512 biases, which
    # are state
    assert sum(v.size for v in state[0].values()) == 1_642_962_816
    assert len(state[1]) == 12
    assert plan.argument_size_in_bytes > 9.1 * 2 ** 30
    assert plan.alias_size_in_bytes > 9.1 * 2 ** 30        # donated
    peak = (plan.argument_size_in_bytes + plan.output_size_in_bytes
            - plan.alias_size_in_bytes + plan.temp_size_in_bytes)
    # what memory_stats()["bytes_limit"] reads on the chip (PR 34)
    assert peak < (15.748 - 3.0) * 2 ** 30, peak / 2 ** 30
    assert list(moe_ops.moe_plans()) == [
        "t4096 k22 e16/512 w1024 i2688 relu2 bfloat16"]
    assert moe_ops.moe_plans()[
        "t4096 k22 e16/512 w1024 i2688 relu2 bfloat16"]["buffer_rows"] == 7040
    plan, conv = ssm_ops.ssm_plans().values()
    assert list(ssm_ops.ssm_plans()) == [
        "b1 t4096 h128x64 g8 n128 bfloat16",
        "conv b1 t4096 c10240 k4 bfloat16"]
    assert (conv["route"], conv["grid_steps_bwd"]) == ("kernel", 80)
    assert plan == {
        "chunk": 128, "chunks": 32, "heads_a_step": 16, "route": "kernel",
        "state_bytes_saved": 4 * 32 * 128 * 64 * 128, "padded_rows": 0,
        "grid_steps_fwd": 256, "grid_steps_bwd": 256,
        "vmem_bytes": plan["vmem_bytes"]}
    text = compiled.as_text()
    assert "rematted_computation" in text
    # five mixer layers: the scan's forward, again, and its backward
    assert text.count("ssd_scan_fwd") >= 10 and "ssd_scan_bwd" in text
    # and the convolution's, the pair's
    assert text.count("causal_conv1d_fwd") >= 10
    assert text.count("causal_conv1d_bwd") >= 5
    # 2 attention layers forward, again and backward; 6 routed layers'
    # grouped matmuls
    assert text.count("flash_attention_fwd") >= 4
    assert "moe_experts_fwd" in text and "moe_experts_bwd_dw" in text


def _embedding_fwd_bwd(one_chip, ids, rows, width):
    """`Embedding` forward and the gradient by its table for a cotangent
    that is an argument (a constant one would fold away), compiled."""
    from incubator_mxnet_tpu.ops import index_ops

    def run(weight, data, g):
        out, vjp = jax.vjp(lambda w: index_ops.embedding.fn(data, w), weight)
        return out, vjp(g)[0]

    with xc.compile_cache_bypassed():
        return jax.jit(run).lower(
            _spec(one_chip, (rows, width), BF16), _spec(one_chip, (ids,), I32),
            _spec(one_chip, (ids, width), BF16)).compile()


def test_embedding_grad_at_falcons_signature_is_one_matmul(one_chip,
                                                           for_the_chip):
    """4,096 ids into `[32640, 5120]`: the gradient by the table is one
    convolution fusion with the one-hot's compare fused into its operand --
    no scatter, no `[4096, 32640]` array among the entry computation's
    instructions, no temporary as large as the gradient (PERF.md section 6,
    PR 35)."""
    compiled = _embedding_fwd_bwd(one_chip, 4096, 32640, 5120)
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    assert "scatter" not in text
    assert len([line for line in entry.splitlines()
                if " fusion(" in line and "convolution" in line]) == 1
    assert "[4096,32640]" not in entry and "[32640,4096]" not in entry
    assert "pred[4096,32640]" in text          # inside the fusion
    assert compiled.memory_analysis().temp_size_in_bytes < 32640 * 5120 * 2


def test_embedding_grad_at_berts_signature_stays_the_scatter(one_chip,
                                                             for_the_chip):
    text = _embedding_fwd_bwd(one_chip, 16384, 30522, 768).as_text()
    assert "scatter-add" in text and "convolution" not in text
    assert "[16384,30522]" not in text


@pytest.mark.parametrize("ids,rows,width", [
    (16384, 30522, 768), (8194, 16160, 2048), (4096, 32640, 4096),
    (4096, 32640, 5120), (4096, 32640, 4224), (4096, 32640, 4352),
    (4096, 32640, 7808), (4096, 32640, 7936),
    (4080, 32640, 5120), (4096, 32768, 5120), (8192, 129280, 2048)])
def test_xla_scatter_forms_are_where_the_route_says(one_chip, ids, rows,
                                                    width):
    """`embedding_grad_route` models three compiled forms of the gather's
    transpose and tells them apart by shape.  The compiler's own choice,
    read from the program: a `sort` where the ids are sorted, a second
    custom fusion where the updates are gathered first, neither where the
    table is updated in place.  An XLA that draws the lines elsewhere
    fails here, and the route's constants want a new sweep."""
    from incubator_mxnet_tpu.ops import index_ops

    def grad(data, g):
        _, vjp = jax.vjp(
            lambda w: jnp.take(w, data, axis=0, mode="clip"),
            jnp.zeros((rows, width), BF16))
        return vjp(g)[0]

    text = _compile(grad, _spec(one_chip, (ids,), I32),
                    _spec(one_chip, (ids, width), BF16))
    entry = text[text.index("\nENTRY "):]
    fusions = len([line for line in entry.splitlines()
                   if "kind=kCustom" in line])
    form = ("in_place" if " sort(" not in entry else
            "sorted_gathered" if fusions == 2 else "sorted")
    assert form == index_ops.embedding_grad_route(
        ids, rows, width, BF16)["scatter_form"]


def test_mosaic_kernel_under_a_mesh_needs_gspmd_trace(topo, for_the_chip):
    """Why `fuse.FusedTrainStep` traces its step under `gspmd_trace` when it
    is given a mesh: GSPMD cannot partition a Mosaic kernel, so a dp program
    that reaches one does not compile; inside `gspmd_trace` the op routes
    to its XLA composition and the program partitions."""
    from incubator_mxnet_tpu.ops import nn_ops
    mesh = Mesh(onp.array(topo.devices).reshape(4), ("dp",))
    logits = _spec(NamedSharding(mesh, P("dp")), (256, 1000), F32)
    labels = _spec(NamedSharding(mesh, P("dp")), (256,), I32)

    def mean_loss(x, y):
        return jnp.mean(nn_ops.softmax_xent.fn(x, y))

    with pytest.raises(NotImplementedError, match="automatically partitioned"):
        _compile(jax.grad(mean_loss), logits, labels)

    def mean_loss_over_mesh(x, y):
        with pk.gspmd_trace():
            return mean_loss(x, y)

    text = _compile(jax.value_and_grad(mean_loss_over_mesh), logits, labels)
    assert "tpu_custom_call" not in text
    assert "all-reduce" in text         # the mean over the sharded batch
