"""Fused sepconv kernel + Xception fast path, validated on CPU.

The Pallas kernel runs in interpret mode here (tests are hermetic-CPU,
conftest.py); speed is the benchmark's business (perfbench/).  What IS pinned
here: kernel-vs-reference numerics, BN folding against flax.linen.BatchNorm
(including the Keras-parity epsilon), batch-tile picking rules, and the
full fast-forward's logits against the stock flax graph on the same
variables.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetes_deep_learning_tpu.models import build_forward, init_variables
from kubernetes_deep_learning_tpu.modelspec import ModelSpec, register_spec
from kubernetes_deep_learning_tpu.ops import fused_sepconv
from kubernetes_deep_learning_tpu.ops.fused_sepconv import (
    CHAIN_VMEM_LIMIT_BYTES,
    chain_batch_tile,
    chain_vmem_bytes,
    fold_bn,
    fused_sepconv_block,
    middle_block_weights,
    pick_batch_tile,
    sepconv_block_reference,
)


def _random_block_weights(rng, c):
    dw = jnp.asarray(rng.normal(0, 0.2, (3, 3, 3, c)), jnp.float32)
    pw = jnp.asarray(rng.normal(0, 0.05, (3, c, c)), jnp.bfloat16)
    s = jnp.asarray(rng.uniform(0.8, 1.2, (3, c)), jnp.float32)
    b = jnp.asarray(rng.normal(0, 0.1, (3, c)), jnp.float32)
    return dw, pw, s, b


@pytest.mark.parametrize(
    "shape",
    [
        (4, 6, 6, 256),
        (2, 5, 7, 128),
        # non-8-multiple batches (serving buckets whose tile Mosaic refused)
        # run via sublane padding and must match on the real rows
        (1, 6, 6, 128),
        (3, 6, 6, 128),
        (6, 6, 6, 128),
    ],
)
def test_kernel_matches_reference(shape):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(0, 1, shape), jnp.bfloat16)
    dw, pw, s, b = _random_block_weights(rng, shape[-1])
    want = np.asarray(sepconv_block_reference(x, dw, pw, s, b), np.float32)
    got = np.asarray(
        jax.jit(lambda *a: fused_sepconv_block(*a, interpret=True))(x, dw, pw, s, b),
        np.float32,
    )
    assert got.shape == shape
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"kernel diverges from reference: {rel:.2e}"


def test_fold_bn_matches_flax_batchnorm():
    import flax.linen as nn

    from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS, batch_norm

    rng = np.random.default_rng(1)
    c = 32
    x = jnp.asarray(rng.normal(0, 1, (4, c)), jnp.float32)
    p = {
        "scale": jnp.asarray(rng.uniform(0.8, 1.2, c), jnp.float32),
        "bias": jnp.asarray(rng.normal(0, 0.1, c), jnp.float32),
    }
    s = {
        "mean": jnp.asarray(rng.normal(0, 0.5, c), jnp.float32),
        "var": jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32),
    }
    mod = batch_norm(False, None, "bn")
    want = mod.apply({"params": p, "batch_stats": s}, x)
    scale, shift = fold_bn(p, s)  # defaults to the Keras epsilon
    got = x * scale + shift
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-5)
    # and it is the KERAS epsilon, not flax's 1e-5 default
    assert KERAS_BN_EPS == 1e-3
    bad_scale, _ = fold_bn(p, s, eps=1e-5)
    assert not np.allclose(np.asarray(bad_scale), np.asarray(scale))


def test_pick_batch_tile_rules():
    # divisible batches take the largest tile under budget
    assert pick_batch_tile(256, 19, 19, 728) == 16
    assert pick_batch_tile(8, 19, 19, 728) == 8
    # huge spatial extents fall back to the smallest aligned tile
    assert pick_batch_tile(256, 74, 74, 728) == 8
    # NEVER a non-8-multiple: Mosaic rejects the kernel's (H, W, bt) row
    # collapse for unaligned bt (a batch-1 tile failed on the v5e).  Unaligned
    # batches are padded by the kernel wrappers, which then see a multiple
    # of 8 -- but pick_batch_tile itself must stay safe for any input.
    assert pick_batch_tile(6, 19, 19, 728) == 8
    assert pick_batch_tile(12, 19, 19, 728) == 8
    assert pick_batch_tile(1, 19, 19, 728) == 8


@pytest.mark.parametrize("batch", [1, 8, 16, 24, 64, 256, 512])
def test_chain_rule_at_the_cell_shapes(batch):
    """The deciding function at Xception 299x299 (the benchmark's cell runs
    batch 512): block 2 reads 64 channels, half the lanes, and stays on XLA
    at every batch; blocks 3 (74x74, 128 -> 256) and 4 (37x37, 256 -> 728)
    hold their whole extent at the batch tile 8 and chain from a padded
    batch of 256 on -- what the chip has timed and seen win; the small
    buckets (64 runs as 16-image chunks, each deciding for itself) keep
    the XLA entry flow."""
    from kubernetes_deep_learning_tpu.models.xception_fast import (
        chained_entry_blocks,
        fused_blocks,
    )

    padded = batch + (-batch) % 8
    tile = 8 if batch >= 256 else 0
    assert chain_batch_tile(padded, 147, 147, (64, 128, 128)) == 0
    assert chain_batch_tile(padded, 74, 74, (128, 256, 256)) == tile
    assert chain_batch_tile(padded, 37, 37, (256, 728, 728)) == tile
    assert chained_entry_blocks((299, 299), batch) == ({3: 8, 4: 8} if tile else {})
    spec = ModelSpec(name="x299", family="xception", input_shape=(299, 299, 3),
                     labels=("a",), preprocessing="tf")
    assert fused_blocks(spec, batch) == [f"block{i}" for i in range(3 if tile else 5, 15)]


def test_chain_rule_is_the_tile_against_the_vmem_limit():
    # what the rule compares: tiles in and out twice, weights twice, the
    # widest float32 value; widths counted in whole 128-lane tiles
    rows = 37 * 37 * 8
    weights = 2 * ((9 * 256 * 4 + 256 * 768 * 2 + 2 * 768 * 4)
                   + (9 * 768 * 4 + 768 * 768 * 2 + 2 * 768 * 4))
    assert chain_vmem_bytes(37, 37, 8, (256, 728, 728)) == (
        2 * rows * (256 + 768) * 2 + weights + rows * 768 * 4
    )
    # block 3's tile at bt=8 is inside the limit with the rule's 5% to
    # spare (Mosaic compiles it at 110 MiB and not at 96), its bt=16 tile
    # and block 2's at full width are far outside
    assert 1.05 * chain_vmem_bytes(74, 74, 8, (128, 256, 256)) <= CHAIN_VMEM_LIMIT_BYTES
    assert chain_vmem_bytes(74, 74, 16, (128, 256, 256)) > CHAIN_VMEM_LIMIT_BYTES
    assert chain_batch_tile(512, 147, 147, (128, 128, 128)) == 0
    # a small extent takes the larger tile where the batch divides by it
    assert chain_batch_tile(256, 12, 12, (256, 728, 728)) == 16
    assert chain_batch_tile(264, 12, 12, (256, 728, 728)) == 8
    assert chain_batch_tile(248, 12, 12, (256, 728, 728)) == 0  # under the least batch
    # and an extent between the flagship's blocks 2 and 3 goes back to XLA
    assert chain_batch_tile(512, 90, 90, (128, 256, 256)) == 0


def _downsample_block_weights(rng, c_in, c_out, block="blockX"):
    """One downsample block's leaves as the flax tree names them."""
    p, s = {}, {}

    def bn(name, c):
        p[name] = {"scale": rng.uniform(0.8, 1.2, c).astype(np.float32),
                   "bias": rng.normal(0, 0.1, c).astype(np.float32)}
        s[name] = {"mean": rng.normal(0, 0.1, c).astype(np.float32),
                   "var": rng.uniform(0.5, 1.5, c).astype(np.float32)}

    p[f"{block}_res_conv"] = {
        "kernel": rng.normal(0, c_in ** -0.5, (1, 1, c_in, c_out)).astype(np.float32)}
    bn(f"{block}_res_bn", c_out)
    for j, ci in ((1, c_in), (2, c_out)):
        p[f"{block}_sepconv{j}"] = {
            "depthwise": {"kernel": rng.normal(0, 0.3, (3, 3, 1, ci)).astype(np.float32)},
            "pointwise": {
                "kernel": rng.normal(0, ci ** -0.5, (1, 1, ci, c_out)).astype(np.float32)},
        }
        bn(f"{block}_sepconv{j}_bn", c_out)
    return p, s


def _downsample_block_xla(x, p, s, block="blockX"):
    """The XLA arrangement of the same block, NHWC bf16, written out here:
    relu -> sepconv -> bn twice, max_pool SAME 3x3/2, strided 1x1 residual."""
    import flax.linen as nn

    from kubernetes_deep_learning_tpu.models.layers import KERAS_BN_EPS

    bf = jnp.bfloat16

    def conv(x, kernel, stride=1, groups=1):
        return jax.lax.conv_general_dilated(
            x.astype(bf), jnp.asarray(kernel, bf), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), feature_group_count=groups)

    def bn(x, name):
        mean, var = (jnp.asarray(s[name][k], bf) for k in ("mean", "var"))
        scale, bias = (jnp.asarray(p[name][k], bf) for k in ("scale", "bias"))
        return (x - mean) * jax.lax.rsqrt(var + jnp.asarray(KERAS_BN_EPS, bf)) * scale + bias

    residual = bn(conv(x, p[f"{block}_res_conv"]["kernel"], stride=2), f"{block}_res_bn")
    for j in (1, 2):
        sep = p[f"{block}_sepconv{j}"]
        x = conv(nn.relu(x), sep["depthwise"]["kernel"], groups=x.shape[-1])
        x = bn(conv(x, sep["pointwise"]["kernel"]), f"{block}_sepconv{j}_bn")
    return nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME") + residual


@pytest.mark.parametrize("batch", [1, 8, 24])
@pytest.mark.parametrize("extent", [9, 10])
def test_downsample_t_matches_the_xla_block(extent, batch):
    """downsample_t (the fused chain + pool + residual of blocks 3, 4, 13)
    against the XLA arrangement of the same block, at an odd and an even
    extent (9 -> 5, 10 -> 5: SAME pooling pads differently) and at batches
    the kernel pads (1), takes whole (8) and walks in three tiles (24)."""
    from kubernetes_deep_learning_tpu.models.xception_fast import downsample_t

    rng = np.random.default_rng(31 + extent + batch)
    c_in, c_out = 128, 256
    p, s = _downsample_block_weights(rng, c_in, c_out)
    x = jnp.asarray(rng.normal(0, 1, (batch, extent, extent, c_in)), jnp.bfloat16)
    want = np.asarray(_downsample_block_xla(x, p, s), np.float32)

    got_t = jax.jit(lambda xt: downsample_t(xt, p, s, "blockX", bt=8, interpret=True))(
        x.transpose(1, 2, 0, 3))
    got = np.asarray(got_t.transpose(2, 0, 1, 3), np.float32)
    assert got.shape == want.shape == (batch, 5, 5, c_out)
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"downsample_t diverges from the XLA block: {rel:.2e}"


def test_fused_entry_kernel_matches_reference():
    """The fused entry kernel (conv2 + block2, ops.fused_entry) vs its
    plain-jnp reference at a small parameterized geometry, interpret mode:
    pins the halo/mask/stride-selection math, including a final partial
    row tile (h_out=11, rt=4) and the batch-pad path (B=2 -> 8)."""
    from kubernetes_deep_learning_tpu.ops.fused_entry import (
        entry_block_reference,
        fused_entry_block_t,
    )

    rng = np.random.default_rng(3)
    h_in, c_in, c_b, c_out = 23, 8, 16, 32  # h_b=21, h_out=11
    w = {
        "conv2": rng.normal(0, 0.2, (3, 3, c_in, c_b)).astype(np.float32),
        "conv2_s": rng.uniform(0.8, 1.2, c_b).astype(np.float32),
        "conv2_b": rng.normal(0, 0.1, c_b).astype(np.float32),
        "res": rng.normal(0, 0.1, (c_b, c_out)).astype(np.float32),
        "res_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "res_b": rng.normal(0, 0.1, c_out).astype(np.float32),
        "dw1": rng.normal(0, 0.2, (3, 3, c_b)).astype(np.float32),
        "pw1": rng.normal(0, 0.1, (c_b, c_out)).astype(np.float32),
        "bn1_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "bn1_b": rng.normal(0, 0.1, c_out).astype(np.float32),
        "dw2": rng.normal(0, 0.2, (3, 3, c_out)).astype(np.float32),
        "pw2": rng.normal(0, 0.1, (c_out, c_out)).astype(np.float32),
        "bn2_s": rng.uniform(0.8, 1.2, c_out).astype(np.float32),
        "bn2_b": rng.normal(0, 0.1, c_out).astype(np.float32),
    }
    w = {k: jnp.asarray(v) for k, v in w.items()}
    for batch in (2, 8):  # 2 exercises the pad-to-8 assert path upstream
        a = jnp.asarray(rng.normal(0, 0.5, (8, h_in, h_in, c_in)), jnp.bfloat16)
        a = a[:batch] if batch < 8 else a
        want = np.asarray(entry_block_reference(a, w), np.float32)
        a_t = jnp.pad(a, ((0, 8 - batch), (0, 0), (0, 0), (0, 0))).transpose(
            1, 2, 0, 3
        )
        got_t = jax.jit(
            lambda xt: fused_entry_block_t(xt, w, rt=4, interpret=True)
        )(a_t)
        got = np.asarray(got_t.transpose(2, 0, 1, 3)[:batch], np.float32)
        assert got.shape == want.shape
        rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
        assert rel < 2e-2, f"entry kernel diverges (batch {batch}): {rel:.2e}"


def test_fast_forward_entry_kernel_matches_flax(fast_spec):
    """The EXPERIMENTAL entry_kernel=True fast path end to end (fused entry
    + block3/4 chains + middle + exit, interpret mode) vs the stock flax
    graph -- kept tested even though serving does not enable it."""
    from kubernetes_deep_learning_tpu.models.xception_fast import build_fast_forward
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    rng = np.random.default_rng(5)
    variables = jax.tree_util.tree_map(np.asarray, init_variables(fast_spec, seed=4))
    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    ref = jax.jit(build_forward(fast_spec, dtype=jnp.bfloat16, fast=False))
    want = np.asarray(ref(variables, images))

    fast = build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, entry_kernel=True
    )
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)
    got = np.asarray(jax.jit(fast)(variables, x), np.float32)

    # 2e-2: the pallas interpreter's bf16 accumulation rounds slightly
    # differently from the XLA graph; the compiled Mosaic kernels are held
    # to the tighter bound chip_smoke.py states against the f32 graph.
    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"entry-kernel fast path diverges from flax: {rel:.2e}"

    # conv1_t variant (VERDICT r3 #5): conv1 computed in (H, W, B, C) via
    # HWNC dimension_numbers must be numerically identical layout-math.
    fast_t = build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, entry_kernel=True,
        conv1_t=True,
    )
    got_t = np.asarray(jax.jit(fast_t)(variables, x), np.float32)
    rel = np.abs(got_t - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 2e-2, f"conv1_t fast path diverges from flax: {rel:.2e}"


@pytest.fixture(scope="module")
def fast_spec():
    return register_spec(
        ModelSpec(
            name="fast-xception",
            family="xception",
            input_shape=(96, 96, 3),
            labels=("a", "b", "c", "d"),
            preprocessing="tf",
            head_hidden=(16,),
        )
    )


def _pallas_calls(jaxpr) -> int:
    """pallas_call equations of a traced program, inner jaxprs included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "pallas_call"
        for v in eqn.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                n += _pallas_calls(inner)
    return n


@pytest.mark.parametrize(
    "entry",
    [
        None,       # the rule's own answer at the test spec: blocks 3 and 4 chained
        {},         # a batch or extent the rule leaves to XLA
        {3: 8},     # block 3 alone: back to NHWC for block 4, then (H, W, B, C) again
    ],
    ids=["rule", "xla-entry", "block3-alone"],
)
def test_fast_forward_matches_flax(fast_spec, monkeypatch, entry):
    """Full fast path (conv1/conv2/block 2 lax ops, entry chains where the
    rule sends them, fused middle and exit; interpret mode) vs the stock
    flax graph on identical variables with jittered BN stats."""
    from kubernetes_deep_learning_tpu.models import xception_fast
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    if entry is None:
        # 96x96: block 3 sees 23x23x128, block 4 12x12x256 -- both chain,
        # once the batch the interpreter can afford counts as large enough
        monkeypatch.setattr(fused_sepconv, "CHAIN_MIN_BATCH", 8)
        assert xception_fast.chained_entry_blocks((96, 96), 2) == {3: 8, 4: 8}
    else:
        monkeypatch.setattr(
            xception_fast, "chained_entry_blocks", lambda hw, batch: dict(entry)
        )

    rng = np.random.default_rng(2)
    variables = jax.tree_util.tree_map(np.asarray, init_variables(fast_spec, seed=3))

    def jitter(tree):
        for k, v in tree.items():
            if isinstance(v, dict):
                jitter(v)
            elif k == "mean":
                tree[k] = rng.normal(0, 0.05, v.shape).astype(np.float32)
            elif k == "var":
                tree[k] = rng.uniform(0.5, 1.5, v.shape).astype(np.float32)

    jitter(variables["batch_stats"])

    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    ref = jax.jit(build_forward(fast_spec, dtype=jnp.bfloat16, fast=False))
    want = np.asarray(ref(variables, images))

    fast = xception_fast.build_fast_forward(fast_spec, dtype=jnp.bfloat16, interpret=True)
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)
    got = np.asarray(jax.jit(fast)(variables, x), np.float32)

    rel = np.abs(got - want).max() / (np.abs(want).max() + 1e-6)
    assert rel < 1e-2, f"fast path diverges from flax graph: {rel:.2e}"

    # what the forward traced is what the status page will name: one
    # pallas_call a fused block (8 middle, 13, 14, and the entry chains)
    traced = _pallas_calls(jax.make_jaxpr(fast)(variables, x).jaxpr)
    assert traced == len(xception_fast.fused_blocks(fast_spec, 2)) == 10 + len(
        xception_fast.chained_entry_blocks((96, 96), 2)
    )


def test_chunk_size_rules():
    """Microbatch chunking engages exactly for 8-multiples in [32, 64]
    (measured win zone, exp/chunked_forward.py); everything else
    monolithic.  Non-16-multiples take a trailing 8-chunk."""
    from kubernetes_deep_learning_tpu.models.xception_fast import _chunk_sizes

    assert _chunk_sizes(32) == [16, 16]
    assert _chunk_sizes(40) == [16, 16, 8]
    assert _chunk_sizes(48) == [16, 16, 16]
    assert _chunk_sizes(56) == [16, 16, 16, 8]
    assert _chunk_sizes(64) == [16, 16, 16, 16]
    for n in (1, 8, 16, 24, 36, 96, 128, 256):
        assert _chunk_sizes(n) is None, n


def test_chunked_fast_forward_matches_monolithic(fast_spec, monkeypatch):
    """The chunk wrapper (slice -> forward_one -> concat) must be a pure
    batching identity.  Scaled down (chunk=1 over batch 2) so interpret-mode
    cost stays test-sized; the production chunk geometry (16 over 32-64) is
    run by no cell of the benchmark (ROADMAP D17)."""
    from kubernetes_deep_learning_tpu.models import xception_fast
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize

    monkeypatch.setattr(xception_fast, "_CHUNK", 1)
    monkeypatch.setattr(xception_fast, "_TAIL", 1)
    monkeypatch.setattr(xception_fast, "_CHUNK_MIN", 2)
    monkeypatch.setattr(xception_fast, "_CHUNK_MAX", 2)
    monkeypatch.setattr(fused_sepconv, "CHAIN_MIN_BATCH", 8)  # chains in every chunk

    rng = np.random.default_rng(5)
    variables = init_variables(fast_spec, seed=1)
    images = rng.integers(0, 256, (2, *fast_spec.input_shape), np.uint8)
    x = normalize(jnp.asarray(images), fast_spec.preprocessing)

    mono = xception_fast.build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, chunk=False
    )
    chunked = xception_fast.build_fast_forward(
        fast_spec, dtype=jnp.bfloat16, interpret=True, chunk=True
    )
    want = np.asarray(jax.jit(mono)(variables, x), np.float32)
    got = np.asarray(jax.jit(chunked)(variables, x), np.float32)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # every chunk decides for itself and runs its own kernels, the entry
    # chains among them: twice the monolith's pallas_calls, the same names
    blocks = xception_fast.fused_blocks(fast_spec, 2)
    assert blocks[:2] == ["block3", "block4"] and len(blocks) == 12
    assert _pallas_calls(jax.make_jaxpr(chunked)(variables, x).jaxpr) == 2 * len(blocks)
    assert _pallas_calls(jax.make_jaxpr(mono)(variables, x).jaxpr) == len(blocks)


def test_middle_block_weights_shapes(fast_spec):
    variables = init_variables(fast_spec, seed=0)
    dw, pw, s, b = middle_block_weights(
        variables["params"], variables["batch_stats"], "block5"
    )
    assert dw.shape == (3, 3, 3, 728) and dw.dtype == jnp.float32
    assert pw.shape == (3, 728, 728) and pw.dtype == jnp.bfloat16
    assert s.shape == (3, 728) and b.shape == (3, 728)


def test_build_forward_fast_flag_dispatch(fast_spec):
    """fast='auto' on the CPU backend must stay on the flax graph (pallas
    TPU kernels cannot lower for CPU outside interpret mode)."""
    fwd = build_forward(fast_spec, dtype=jnp.bfloat16)  # auto
    images = np.zeros((1, *fast_spec.input_shape), np.uint8)
    variables = init_variables(fast_spec, seed=0)
    out = jax.jit(fwd)(variables, images)
    assert out.shape == (1, fast_spec.num_classes)


def test_status_page_names_the_fused_blocks_the_forward_traced(fast_spec, monkeypatch, tmp_path):
    """GET /v1/models' ``fused_blocks`` (engine.device_info, beside
    ``fast_engaged``): by warmed bucket, the blocks that run as Pallas
    kernels -- as many as the bucket's traced program holds pallas_calls,
    from the function the forward itself asks.  The kernels run in
    interpret mode here, which the engine never asks for: the test swaps
    the builder in, as a chip would compile them."""
    import functools

    from kubernetes_deep_learning_tpu.export import export_model, load_artifact
    from kubernetes_deep_learning_tpu.export.artifact import version_dir
    from kubernetes_deep_learning_tpu.models import xception_fast
    from kubernetes_deep_learning_tpu.ops.preprocess import normalize
    from kubernetes_deep_learning_tpu.runtime.engine import InferenceEngine

    monkeypatch.setattr(
        xception_fast, "build_fast_forward",
        functools.partial(xception_fast.build_fast_forward, interpret=True),
    )
    monkeypatch.setattr(fused_sepconv, "CHAIN_MIN_BATCH", 8)
    variables = init_variables(fast_spec, seed=0)
    export_model(fast_spec, variables, str(tmp_path), dtype=jnp.bfloat16)
    artifact = load_artifact(version_dir(str(tmp_path), fast_spec.name, 1))
    eng = InferenceEngine(artifact, buckets=(1, 8), use_exported=False, fast=True)
    assert eng.device_info()["fused_blocks"] == {}  # nothing warmed yet
    eng.warmup()
    info = eng.device_info()
    assert info["fast_engaged"] is True and info["fast_degraded"] is False
    names = ["block3", "block4", *(f"block{i}" for i in range(5, 15))]
    assert info["fused_blocks"] == {"1": names, "8": names}

    fast = xception_fast.build_fast_forward(fast_spec, dtype=jnp.bfloat16)
    x = normalize(jnp.zeros((8, *fast_spec.input_shape), jnp.uint8), fast_spec.preprocessing)
    assert _pallas_calls(jax.make_jaxpr(fast)(variables, x).jaxpr) == len(names)

    # off the fused path the page names none
    eng._fast_engaged = False
    assert eng.device_info()["fused_blocks"] == {}


@pytest.fixture(scope="module")
def v5e_chip():
    """One chip of a described v5e: the TPU's compiler without the chip
    (only ever asked for inside a test: one process may hold libtpu)."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.slow  # ~45 s a chain: Mosaic unrolls the whole-extent tile
@pytest.mark.parametrize(
    "h,widths", [(74, (128, 256, 256)), (37, (256, 728, 728))], ids=["block3", "block4"]
)
def test_entry_chains_compile_for_v5e_at_the_rules_tile(v5e_chip, h, widths):
    """What the rule admits must compile: a Mosaic refusal at warm-up would
    degrade the whole engine to the flax graph.  The Xception 299x299 entry
    chains, at the batch tile and VMEM limit the forward gives them."""
    from kubernetes_deep_learning_tpu.ops.fused_sepconv import fused_sepconv_chain_t

    batch = 256
    bt = chain_batch_tile(batch, h, h, widths)
    assert bt == 8

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)

    stages = [
        {"dw": shape((3, 3, ci), jnp.float32), "pw": shape((ci, co), jnp.bfloat16),
         "scale": shape((co,), jnp.float32), "shift": shape((co,), jnp.float32)}
        for ci, co in zip(widths, widths[1:])
    ]

    def chain(x, stages):
        return fused_sepconv_chain_t(
            x, [dict(s, pre_relu=True, post_relu=False) for s in stages],
            bt=bt, vmem_limit_bytes=CHAIN_VMEM_LIMIT_BYTES)

    compiled = jax.jit(chain).lower(shape((h, h, batch, widths[0]), jnp.bfloat16), stages).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("slots", [128, 127], ids=["two-slots-a-step", "one-slot-a-step"])
def test_the_latent_attention_kernel_compiles_for_v5e_at_the_token_cells_shapes(v5e_chip, slots):
    """The generative lane's paged latent-attention kernel (``ops/mla_decode.py``;
    here because one test file may hold the TPU's compiler) at the shapes of
    ``longcat-agent-decode-closed128``: a Mosaic refusal would surface only at
    the lane's warm-up on the chip.  The result and the first operand are what
    the benchmark's ``mla_decode_roofline.lc`` finds the call by."""
    from kubernetes_deep_learning_tpu.ops.mla_decode import paged_mla_attention

    def shape(s, d):
        return jax.ShapeDtypeStruct(s, d, sharding=v5e_chip)

    heads, width, rank, page, max_pages = 64, 640, 512, 16, 96
    compiled = jax.jit(lambda q, cache, table, n: paged_mla_attention(
        q, cache, 3, table, n, rank=rank)).lower(
        shape((slots, heads, width), jnp.bfloat16),
        shape((8, 1 + slots * max_pages, page, width), jnp.bfloat16),
        shape((slots, max_pages), jnp.int32), shape((slots,), jnp.int32)).compile()
    lines = [ln.strip() for ln in compiled.as_text().splitlines()]
    call = [ln for ln in lines if "custom-call(" in ln]
    assert len(call) == 1 and "tpu_custom_call" in call[0]
    assert f"= f32[{slots},{heads},{rank}]" in call[0]
    first_operand = call[0].split("custom-call(")[1].split(",")[0]
    assert any(ln.startswith(f"{first_operand} = s32[{slots * max_pages}]") for ln in lines)
