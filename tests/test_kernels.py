"""Per-kernel shape/dtype sweeps, allclose vs the pure-jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("shape", [(17, 130), (256, 512), (3, 5, 384)])
@pytest.mark.parametrize("bits", [4, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_matches_ref(shape, bits, dtype):
    x = (jax.random.normal(jax.random.PRNGKey(0), shape) * 3).astype(dtype)
    q1 = ops.quantize(x, -9.0, 9.0, bits=bits)
    q2 = ref.quantize_ref(x, -9.0, 9.0, bits=bits)
    # rounding of values exactly at .5 boundaries may differ by 1 code in
    # low-precision dtypes; require exactness in f32
    if dtype == jnp.float32:
        assert jnp.all(q1 == q2)
    else:
        assert jnp.max(jnp.abs(q1.astype(jnp.int32) - q2.astype(jnp.int32))) <= 1


@pytest.mark.parametrize("bits", [4, 8])
def test_dequantize_matches_ref(bits):
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 257)) * 2
    q = ref.quantize_ref(x, -7.0, 7.0, bits=bits)
    d1 = ops.dequantize(q, -7.0, 7.0, bits=bits)
    d2 = ref.dequantize_ref(q, -7.0, 7.0, bits=bits)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                               rtol=1e-5, atol=1e-5)


def test_quant_roundtrip_error_bound():
    """Round-off error is bounded by half a quantization step (Eq. 1-2)."""
    x = jax.random.uniform(jax.random.PRNGKey(2), (128, 256),
                           minval=-5.0, maxval=5.0)
    for bits in (4, 8):
        q = ops.quantize(x, -5.0, 5.0, bits=bits)
        d = ops.dequantize(q, -5.0, 5.0, bits=bits)
        step = 10.0 / ((1 << bits) - 1)
        assert float(jnp.max(jnp.abs(d - x))) <= step / 2 + 1e-5


@pytest.mark.parametrize("t,d,dp", [(64, 128, 32), (513, 384, 96),
                                    (100, 260, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bottleneck_encode(t, d, dp, dtype):
    x = jax.random.normal(jax.random.PRNGKey(3), (t, d), dtype)
    w = (jax.random.normal(jax.random.PRNGKey(4), (d, dp)) * 0.05).astype(dtype)
    b1 = ops.bottleneck_encode(x, w, -4.0, 4.0)
    b2 = ref.bottleneck_encode_ref(x, w, -4.0, 4.0)
    diff = jnp.abs(b1.astype(jnp.int32) - b2.astype(jnp.int32))
    assert int(diff.max()) <= 1  # .5-boundary rounding tolerance


def _grouped_inputs(m, k, n, g):
    lhs = jax.random.normal(jax.random.PRNGKey(8), (m, k), jnp.bfloat16)
    rhs = jax.random.normal(jax.random.PRNGKey(9), (g, k, n), jnp.bfloat16)
    return lhs, rhs


def _check_grouped(got, lhs, rhs, sizes):
    """``got`` against the oracle over the groups' rows; rows past them
    zero."""
    m, n = lhs.shape[0], rhs.shape[2]
    want = ref.grouped_matmul_ref(lhs, rhs,
                                  jnp.asarray(sizes[:rhs.shape[0]]))
    assert got.dtype == jnp.float32 and got.shape == (m, n)
    # the same bfloat16 products, summed in float32 in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)
    assert not np.any(np.asarray(got)[sum(sizes[:rhs.shape[0]]):])


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("m,n,sizes", [
    (32, 24, (5, 0, 11, 16)), (32, 24, (32, 0, 0, 0)),
    (32, 24, (3, 4, 5, 6)), (1100, 24, (300, 0, 500, 300)),
    (32, 600, (5, 0, 11, 16))])
def test_grouped_matmul_matches_ref(m, n, sizes, impl):
    """Uneven and empty groups, through ``ops.grouped_matmul``'s own
    tiling (Pallas in interpret mode). Where 14 rows fall past the groups
    a last count names them, and they come out zero. At 1100 rows m is
    padded to a multiple of ``gmm.TM`` and the group boundaries at 300
    and 800 fall inside m-tiles; n = 600 leaves a partial last n-tile
    after one of ``gmm.TN``."""
    lhs, rhs = _grouped_inputs(m, 48, n, 4)
    if sum(sizes) < m:
        sizes += (m - sum(sizes),)
    got = ops.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                             impl=impl)
    _check_grouped(got, lhs, rhs, sizes)


@pytest.mark.parametrize("k,n,sizes,tiles", [
    # a boundary inside each of two 64-row m-tiles, an empty group
    (128, 256, (37, 0, 100, 119), (64, 128, 128)),
    # n = 300 is not a multiple of tn = 128: a partial last n-tile
    (128, 300, (37, 0, 100, 119), (64, 128, 128)),
    # k = 300 in 128-wide k-tiles: the last one is masked
    (300, 256, (64, 64, 0, 128), (64, 128, 128)),
    # 9 rows past the groups, counted last: they come out zero
    (128, 300, (37, 0, 100, 110, 9), (64, 128, 128)),
])
def test_gmm_kernel_tiles(k, n, sizes, tiles):
    """The Pallas kernel at tiles smaller than the shape, in interpret
    mode, against the oracle."""
    from repro.kernels import gmm

    lhs, rhs = _grouped_inputs(256, k, n, 4)
    got = gmm.gmm(lhs, rhs, jnp.asarray(sizes, jnp.int32), tiles, True)
    _check_grouped(got, lhs, rhs, sizes)


@pytest.mark.parametrize("sizes", [(5, 0, 11, 16), (3, 4, 5, 6, 14)])
def test_grouped_matmul_grad_matches_ref(sizes):
    """The gradient through the Pallas path (interpret mode) is the
    oracle's, for the rows and the weights; rows past the groups get
    none."""
    lhs, rhs = _grouped_inputs(32, 48, 24, 4)
    gs = jnp.asarray(sizes, jnp.int32)
    cot = jax.random.normal(jax.random.PRNGKey(10), (32, 24))

    def loss(f):
        return lambda x, w: jnp.sum(f(x, w) * cot)

    got = jax.grad(loss(lambda x, w: ops.grouped_matmul(
        x, w, gs, impl="pallas", interpret=True)), argnums=(0, 1))(lhs, rhs)
    want = jax.grad(loss(lambda x, w: ref.grouped_matmul_ref(
        x, w, gs[:4])), argnums=(0, 1))(lhs, rhs)
    for a, b in zip(got, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=2e-2, atol=2e-2)
    assert not np.any(np.asarray(got[0], np.float32)[sum(sizes[:4]):])


def test_routed_expert_shards_sum_to_all_experts(monkeypatch):
    """``moe._routed`` over two shards of two experts each (``lo`` 0 and
    2), on the interpreted Pallas kernel, sums to all four experts on
    ``ragged_dot``: each shard's kernel clears the other's slots."""
    from repro.models import moe

    t, k, d, f = 40, 2, 32, 48
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    xf = jax.random.normal(keys[0], (t, d), jnp.bfloat16)
    idx = jax.random.randint(keys[1], (t, k), 0, 4)
    w = jax.random.uniform(keys[2], (t, k))
    wg, wi = (jax.random.normal(kk, (4, d, f), jnp.bfloat16) * 0.2
              for kk in jax.random.split(keys[3]))
    wo = jax.random.normal(keys[4], (4, f, d), jnp.bfloat16) * 0.2

    def shard(lo, n):
        return moe._swiglu_experts(wg[lo:lo + n], wi[lo:lo + n],
                                   wo[lo:lo + n])

    want = moe._routed(xf, idx, w, 4, shard(0, 4))
    monkeypatch.setattr(ops, "_backend_impl", lambda: "pallas")
    got = sum(moe._routed(xf, idx, w, 2, shard(lo, 2), lo=lo)
              for lo in (0, 2))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [
    (98304, 2048, 1408), (98304, 1408, 2048),   # the Moonlight cell
    (16384, 7168, 2048), (16384, 2048, 7168),   # Kimi K2's experts
    (8, 1792, 2048),                            # K2 decode, a d-slice of 4
    (32, 48, 24), (1100, 48, 24), (32, 48, 600), (256, 32, 16)])  # tests
def test_gmm_tiling_fits_the_shape(m, k, n):
    """Every tile is the whole dimension or a multiple of the layout's
    (16 rows, 128 lanes) below it, and the blocks fit the VMEM budget, in
    bfloat16 and in float32. The Moonlight cell's shapes take the whole
    of k, so each expert's weights are fetched once, and the sweep's
    (256, k, 512)."""
    from repro.kernels import gmm

    for itemsize in (2, 4):
        tm, tk, tn = gmm.tiling(m, k, n, itemsize)
        assert tm % 16 == 0 and tm <= min(gmm.TM, m + 15)
        assert tk == k or (tk % 128 == 0 and tk < k)
        assert tn == n or (tn % 128 == 0 and tn < n)
        assert gmm.vmem_bytes(tm, tk, tn, itemsize) <= gmm.VMEM_BUDGET
    if m == 98304:
        assert gmm.tiling(m, k, n) == (256, k, 512)


@pytest.mark.parametrize("s", [64, 257, 1024])
@pytest.mark.parametrize("hkv,g", [(2, 4), (1, 8), (4, 1)])
def test_decode_attention(s, hkv, g):
    key = jax.random.PRNGKey(5)
    b, d = 2, 64
    q = jax.random.normal(key, (b, hkv * g, d))
    k = jax.random.normal(jax.random.PRNGKey(6), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(7), (b, s, hkv, d))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    pos = jnp.where(pos % 5 == 2, -1, pos)
    idx = s - 10
    o1 = ops.decode_attention(q, k, v, pos, idx)
    o2 = ref.decode_attention_ref(q, k, v, pos, idx)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_matches_model_flash():
    """Kernel oracle agrees with the model's chunked flash attention."""
    from repro.models.attention import flash_attention
    b, s, hkv, g, d = 2, 128, 2, 2, 32
    q = jax.random.normal(jax.random.PRNGKey(8), (b, 1, hkv * g, d))
    k = jax.random.normal(jax.random.PRNGKey(9), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(10), (b, s, hkv, d))
    pos = jnp.broadcast_to(jnp.arange(s), (b, s))
    idx = s - 1
    o_flash = flash_attention(
        q, k, v, q_positions=jnp.full((b, 1), idx),
        k_positions=pos, causal=True, chunk=64)
    o_ref = ref.decode_attention_ref(q[:, 0], k, v, pos, idx)
    np.testing.assert_allclose(np.asarray(o_flash[:, 0]), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ------------------------------------------------ fused pair scorer (PR 6)
# route-scorer fusion: edge-feature build + occupancy reduction + server
# embed + decomposed pair MLP in one op, raced against the naive oracle
# that mirrors the default entity path op-for-op.

def _pair_scorer_inputs(key, n, e, dtype=jnp.float32):
    """Live-env-magnitude inputs at fleet size n, pool size e."""
    ks = jax.random.split(key, 8)
    ue_emb = jnp.tanh(jax.random.normal(ks[0], (n, 128))).astype(dtype)
    raw = {
        "d": jax.random.uniform(ks[1], (n,), minval=1.0,
                                maxval=100.0).astype(dtype),
        "work": jax.random.uniform(ks[2], (n,), minval=5e7,
                                   maxval=5e8).astype(dtype),
        "active": (jax.random.uniform(ks[3], (n,)) < 0.7).astype(dtype),
        "geom": jax.random.uniform(ks[4], (e, 3), minval=0.5,
                                   maxval=2.0).astype(dtype),
        "consts": jnp.asarray([3.0, 0.5, 1e-9, 0.1, 0.5, e * 2.0,
                               100.0, 1e-12], dtype),
    }
    srv_enc = {"w": jax.random.normal(ks[5], (4, 32)) * 0.5,
               "b": jnp.zeros((32,))}
    scorer = [{"w": jax.random.normal(ks[6], (163, 48)) * 0.1,
               "b": jnp.zeros((48,))},
              {"w": jax.random.normal(ks[7], (48, 1)) * 0.01,
               "b": jnp.zeros((1,))}]
    return ue_emb, raw, srv_enc, scorer


def _pair_ref(ue_emb, raw, srv_enc, scorer):
    return ref.pair_scorer_ref(
        ue_emb, raw["d"], raw["work"], raw["active"], raw["geom"],
        raw["consts"], srv_enc["w"], srv_enc["b"], scorer[0]["w"],
        scorer[0]["b"], scorer[1]["w"], scorer[1]["b"])


@pytest.mark.parametrize("n,e", [(1, 1), (7, 2), (64, 3), (300, 5)])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pair_scorer_matches_ref(n, e, impl):
    """Fused scorer == naive oracle over an N/E grid. N=300 exercises the
    ragged final Pallas block (grid block 256)."""
    args = _pair_scorer_inputs(jax.random.PRNGKey(n * 7 + e), n, e)
    lf, sf = ops.pair_scorer(*args, impl=impl, interpret=True)
    lr, sr = _pair_ref(*args)
    assert lf.shape == (n, e) and sf.shape == sr.shape
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pair_scorer_dtype_grid(dtype, impl):
    """Lower-precision observation blocks go through the same f32 kernel
    accumulation: parity vs the oracle fed the identical rounded inputs."""
    args = _pair_scorer_inputs(jax.random.PRNGKey(11), 33, 3, dtype=dtype)
    lf, _ = ops.pair_scorer(*args, impl=impl, interpret=True)
    lr, _ = _pair_ref(*args)
    assert lf.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(lf), np.asarray(lr),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_pair_scorer_masked_inactive_under_churn(impl):
    """Churn semantics: inactive UEs still get scored rows (the env pins
    them to full-local via feasibility masks, not by dropping rows), and
    the active mask enters ONLY through the per-(server, channel)
    occupancy scalar — so a departure changes every logit through that
    one reduction and nothing else."""
    n, e = 24, 3
    ue_emb, raw, srv_enc, scorer = _pair_scorer_inputs(
        jax.random.PRNGKey(3), n, e)
    for frac in (0.0, 0.5, 1.0):     # empty / half / full fleet
        r = dict(raw, active=(jnp.arange(n) < frac * n).astype(jnp.float32))
        lf, sf = ops.pair_scorer(ue_emb, r, srv_enc, scorer,
                                 impl=impl, interpret=True)
        lr, sr = _pair_ref(ue_emb, r, srv_enc, scorer)
        np.testing.assert_allclose(np.asarray(lf), np.asarray(lr),
                                   rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(np.asarray(sf), np.asarray(sr),
                                   rtol=1e-5, atol=1e-5)
    # two churn states differing ONLY in the mask: occupancy is the sole
    # coupling, so equal occupancy => bitwise-equal logits
    a1 = jnp.zeros((n,)).at[0].set(1.0)
    a2 = jnp.zeros((n,)).at[n - 1].set(1.0)
    l1, _ = ops.pair_scorer(ue_emb, dict(raw, active=a1), srv_enc, scorer,
                            impl=impl, interpret=True)
    l2, _ = ops.pair_scorer(ue_emb, dict(raw, active=a2), srv_enc, scorer,
                            impl=impl, interpret=True)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_pair_scorer_unknown_impl_raises():
    args = _pair_scorer_inputs(jax.random.PRNGKey(0), 4, 2)
    with pytest.raises(ValueError, match="impl"):
        ops.pair_scorer(*args, impl="cuda")


def test_pair_scorer_pallas_gradient_matches_xla():
    """Training differentiates the scorer: the Pallas path takes the XLA
    form's gradient (a Mosaic kernel has no autodiff rule), under vmap
    too, as the MAHPPO loss applies it over a minibatch."""
    ue_emb, raw, srv_enc, scorer = _pair_scorer_inputs(
        jax.random.PRNGKey(5), 40, 3)

    def loss(impl):
        def f(ue, sc):
            lg, sv = jax.vmap(lambda u: ops.pair_scorer(
                u, raw, srv_enc, sc, impl=impl,
                interpret=True if impl == "pallas" else None))(ue)
            return jnp.sum(jnp.sin(lg)) + jnp.sum(sv ** 2)
        return jax.grad(f, argnums=(0, 1))(jnp.stack([ue_emb, -ue_emb]),
                                           scorer)

    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6),
        loss("pallas"), loss("xla"))


# --------------------------------------------- quant impl routing (PR 10)
# quantize/dequantize grew the same dual-impl REPRO_*_IMPL convention as
# pair_scorer: decomposed XLA off-TPU, the Pallas kernel on TPU, env-var
# override. The two impls share the exact elementwise math, so codes must
# be BITWISE equal, not merely close.

@pytest.mark.parametrize("bits", [4, 8])
def test_quantize_impls_bitwise_equal(bits):
    x = jax.random.normal(jax.random.PRNGKey(12), (37, 130)) * 4
    qx = ops.quantize(x, -9.0, 9.0, bits=bits, impl="xla")
    qp = ops.quantize(x, -9.0, 9.0, bits=bits, impl="pallas",
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(qx), np.asarray(qp))
    dx = ops.dequantize(qx, -9.0, 9.0, bits=bits, impl="xla")
    dp = ops.dequantize(qx, -9.0, 9.0, bits=bits, impl="pallas",
                        interpret=True)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dp),
                               rtol=1e-6, atol=1e-6)


def test_quant_impl_env_var(monkeypatch):
    """REPRO_QUANT_IMPL selects the path; an unknown value is an error,
    not a silent fallback. An explicit ``interpret=`` implies Pallas (the
    pre-routing call signature keeps its meaning)."""
    x = jax.random.normal(jax.random.PRNGKey(13), (8, 64))
    monkeypatch.setenv("REPRO_QUANT_IMPL", "xla")
    q_env = ops.quantize(x, -4.0, 4.0)
    np.testing.assert_array_equal(
        np.asarray(q_env), np.asarray(ops.quantize(x, -4.0, 4.0, impl="xla")))
    monkeypatch.setenv("REPRO_QUANT_IMPL", "metal")
    with pytest.raises(ValueError, match="impl"):
        ops.quantize(x, -4.0, 4.0)
    with pytest.raises(ValueError, match="impl"):
        ops.dequantize(q_env, -4.0, 4.0)
    # explicit interpret routes to Pallas regardless of the env var
    q_int = ops.quantize(x, -4.0, 4.0, interpret=True)
    np.testing.assert_array_equal(np.asarray(q_env), np.asarray(q_int))


# ------------------------------------------ fused int8 flat trunk (PR 10)
# serve-small dispatch kernel: dequantize every layer's int8 weight codes
# in-register and run the whole tanh MLP in one fused pass, raced against
# the dequantize-then-matmul oracle.

def _trunk_layers(key, dims=(19, 64, 64, 13), bits=8):
    """Random quantized trunk: per-layer min-max int8 codes + f32 biases
    (the ``rl.distill.quantize_flat_trunk`` layout) and the dequantized
    f32 weights the oracle path sees."""
    qlayers = []
    for i, (d_in, d_out) in enumerate(zip(dims, dims[1:])):
        kw, kb, key = jax.random.split(key, 3)
        w = jax.random.normal(kw, (d_in, d_out)) * 0.4
        mn, mx = float(w.min()), float(w.max())
        qlayers.append({"codes": ref.quantize_ref(w, mn, mx, bits=bits),
                        "mn": jnp.float32(mn), "mx": jnp.float32(mx),
                        "b": jax.random.normal(kb, (d_out,)) * 0.1})
    return qlayers


def _trunk_ref(x, qlayers, bits=8):
    return ref.flat_trunk_ref(
        x, tuple(l["codes"] for l in qlayers),
        tuple(l["mn"] for l in qlayers), tuple(l["mx"] for l in qlayers),
        tuple(l["b"] for l in qlayers), bits=bits)


@pytest.mark.parametrize("shape", [(1, 19), (7, 19), (4, 8, 19), (600, 19)])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_flat_trunk_matches_ref(shape, impl):
    """Fused trunk == naive oracle over batch shapes: batch 1 (the
    dispatch hot path), leading-dim flattening, and 600 rows exercising
    the ragged final Pallas block (block_n 512)."""
    qlayers = _trunk_layers(jax.random.PRNGKey(sum(shape)))
    x = jax.random.normal(jax.random.PRNGKey(1), shape)
    out = ops.flat_trunk(x, qlayers, impl=impl, interpret=True)
    exp = _trunk_ref(x.reshape(-1, shape[-1]), qlayers)
    assert out.shape == shape[:-1] + (13,)
    np.testing.assert_allclose(np.asarray(out).reshape(-1, 13),
                               np.asarray(exp), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("impl", ["xla", "pallas"])
def test_flat_trunk_dtype_grid(dtype, impl):
    """bf16 feature rows accumulate in f32 inside both impls: parity vs
    the oracle fed the identical rounded inputs, f32 head columns out."""
    qlayers = _trunk_layers(jax.random.PRNGKey(2), bits=8)
    x = (jax.random.normal(jax.random.PRNGKey(3), (33, 19)) * 2).astype(dtype)
    out = ops.flat_trunk(x, qlayers, impl=impl, interpret=True)
    exp = _trunk_ref(x, qlayers)
    assert out.dtype == jnp.float32
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=tol, atol=tol)


def test_flat_trunk_impl_env_var(monkeypatch):
    qlayers = _trunk_layers(jax.random.PRNGKey(4))
    x = jax.random.normal(jax.random.PRNGKey(5), (6, 19))
    monkeypatch.setenv("REPRO_FLAT_TRUNK_IMPL", "xla")
    np.testing.assert_allclose(
        np.asarray(ops.flat_trunk(x, qlayers)),
        np.asarray(ops.flat_trunk(x, qlayers, impl="xla")),
        rtol=0, atol=0)
    monkeypatch.setenv("REPRO_FLAT_TRUNK_IMPL", "cuda")
    with pytest.raises(ValueError, match="impl"):
        ops.flat_trunk(x, qlayers)
