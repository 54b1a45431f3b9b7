"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps in interpret mode."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import attention_ref, mha
from repro.kernels.matmul import (grouped_matmul, matmul, matmul_ref,
                                  zorder_matmul)
from repro.kernels.matmul.grouped import gmm_ragged, grouped_blocks
from repro.kernels.matmul.kernel import (VMEM_BUDGET_BYTES, default_blocks,
                                         vmem_working_set_bytes)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 1e-4


def _legal(block, dim):
    """A Pallas TPU block side: a multiple of the 128-wide tiling, or the
    whole dimension."""
    return block % 128 == 0 or block == dim


# (m, k, n, output bytes) of the benchmark's GEMMs: h2o-danube-3-4b's seven
# linears of a 4096-row prefill, and granite-20b's local multiplies on a
# 2x2 torus (Cannon's qkv and o, the ring all-gather's up projection, the
# ring reduce-scatter's down projection into float32)
BENCH_GEMMS = [
    pytest.param(4096, 3840, 3840, 2, id="danube.q"),
    pytest.param(4096, 3840, 960, 2, id="danube.k"),
    pytest.param(4096, 3840, 960, 2, id="danube.v"),
    pytest.param(4096, 3840, 3840, 2, id="danube.o"),
    pytest.param(4096, 3840, 10240, 2, id="danube.gate"),
    pytest.param(4096, 3840, 10240, 2, id="danube.up"),
    pytest.param(4096, 10240, 3840, 2, id="danube.down"),
    pytest.param(2048, 3072, 3200, 4, id="granite.cannon_qkv"),
    pytest.param(2048, 3072, 3072, 4, id="granite.cannon_o"),
    pytest.param(1024, 6144, 6144, 2, id="granite.ring_ag"),
    pytest.param(4096, 6144, 6144, 4, id="granite.ring_rs"),
]


class TestZOrderMatmul:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("shape", [
        (128, 128, 128), (256, 384, 512), (200, 300, 260), (512, 128, 384),
    ])
    def test_against_oracle(self, shape, dtype):
        m, k, n = shape
        a = jax.random.normal(jax.random.PRNGKey(0), (m, k), dtype)
        b = jax.random.normal(jax.random.PRNGKey(1), (k, n), dtype)
        out = matmul(a, b, block_m=128, block_n=128, block_k=128, interpret=True)
        ref = matmul_ref(a, b)
        err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
        scale = jnp.max(jnp.abs(ref.astype(jnp.float32))) + 1e-6
        assert float(err / scale) < _tol(dtype)

    @pytest.mark.parametrize("order", ["zorder", "rowmajor"])
    def test_orders_agree(self, order):
        a = jax.random.normal(jax.random.PRNGKey(2), (256, 256), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(3), (256, 256), jnp.float32)
        out = zorder_matmul(a, b, block_m=128, block_n=128, block_k=128,
                            order=order, interpret=True)
        assert jnp.allclose(out, matmul_ref(a, b), atol=1e-3)

    def test_default_blocks_fit_vmem(self):
        # (4096, 4096, 50000): k is no multiple of 128 and too large to
        # take whole, so it is padded to a multiple of 128
        for dims in [(4096, 4096, 4096), (128, 32768, 256), (8192, 512, 8192),
                     (4096, 4096, 50000)]:
            bm, bn, bk = default_blocks(*dims)
            assert bm % 128 == 0 and bn % 128 == 0 and bk % 128 == 0
            assert vmem_working_set_bytes(bm, bn, bk) <= VMEM_BUDGET_BYTES

    @settings(max_examples=60, deadline=None)
    @given(dims=st.tuples(*[st.sampled_from(
               [128, 512, 960, 3072, 3200, 3840, 4096, 32768])] * 3),
           dtype_bytes=st.sampled_from([1, 2, 4]),
           out_dtype_bytes=st.sampled_from([2, 4]))
    def test_default_blocks_fit_vmem_any_dtype(self, dims, dtype_bytes,
                                               out_dtype_bytes):
        """The heuristic must fit the VMEM budget at the ACTUAL operand and
        output byte widths, not the bf16 defaults -- fp32 operands halve
        the feasible block space."""
        bm, bn, bk = default_blocks(*dims, dtype_bytes, out_dtype_bytes)
        m, n, k = dims
        assert _legal(bm, m) and _legal(bn, n) and _legal(bk, k)
        assert vmem_working_set_bytes(
            bm, bn, bk, dtype_bytes, out_dtype_bytes) <= VMEM_BUDGET_BYTES

    @pytest.mark.parametrize("m,k,n,out_dtype_bytes", BENCH_GEMMS)
    def test_default_blocks_divide_bench_gemms(self, m, k, n,
                                               out_dtype_bytes):
        """At the benchmark's shapes the default blocks divide the GEMM,
        so ``_matmul_jit`` pads nothing, and each block is legal and fits
        the budget."""
        bm, bn, bk = default_blocks(m, n, k, 2, out_dtype_bytes)
        assert m % bm == 0 and n % bn == 0 and k % bk == 0
        assert _legal(bm, m) and _legal(bn, n) and _legal(bk, k)
        assert vmem_working_set_bytes(
            bm, bn, bk, 2, out_dtype_bytes) <= VMEM_BUDGET_BYTES

    @pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
    def test_whole_dimension_block(self, out_dtype):
        """n = 192 is no multiple of 128: the default block takes it
        whole instead of padding it."""
        m, k, n = 256, 384, 192
        obytes = jnp.dtype(out_dtype).itemsize
        assert default_blocks(m, n, k, 2, obytes)[1] == n
        a = jax.random.normal(jax.random.PRNGKey(6), (m, k), jnp.bfloat16)
        b = jax.random.normal(jax.random.PRNGKey(7), (k, n), jnp.bfloat16)
        out = matmul(a, b, interpret=True, out_dtype=out_dtype)
        ref = matmul_ref(a, b, out_dtype=out_dtype)
        assert out.shape == (m, n) and out.dtype == out_dtype
        err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
        scale = jnp.max(jnp.abs(ref.astype(jnp.float32)))
        assert float(err / scale) < _tol(out_dtype)

    def test_tiny_fallback(self):
        a = jax.random.normal(jax.random.PRNGKey(4), (8, 16), jnp.float32)
        b = jax.random.normal(jax.random.PRNGKey(5), (16, 8), jnp.float32)
        assert jnp.allclose(matmul(a, b), a @ b, atol=1e-5)


def _grouped_ref(x, w, sizes):
    """Each group's rows times its weight in float32; rows past the groups
    zero."""
    out = jnp.zeros((x.shape[0], w.shape[2]), jnp.float32)
    start = 0
    for g, size in enumerate(sizes):
        out = out.at[start:start + size].set(jnp.matmul(
            x[start:start + size].astype(jnp.float32),
            w[g].astype(jnp.float32), precision=jax.lax.Precision.HIGHEST))
        start += size
    return out


class TestGroupedMatmul:
    # (rows, k, n, group sizes): ragged groups, empty groups first, last
    # and between, rows past the last group, a group spanning several row
    # tiles, no rows at all, rows no multiple of 16
    @pytest.mark.parametrize("m,k,n,sizes", [
        (256, 128, 256, [0, 100, 0, 37, 90, 0]),
        (48, 128, 192, [3, 0, 2]),
        (1088, 256, 384, [513, 0, 511, 1]),
        (300, 256, 128, [0, 0, 0, 0]),
        (200, 128, 128, [7, 150, 43]),
    ])
    @pytest.mark.parametrize("out_dtype", [jnp.bfloat16, jnp.float32])
    def test_against_float32_product(self, m, k, n, sizes, out_dtype):
        x = jax.random.normal(jax.random.PRNGKey(0), (m, k)).astype(
            jnp.bfloat16)
        w = jax.random.normal(jax.random.PRNGKey(1), (len(sizes), k, n)
                              ).astype(jnp.bfloat16)
        s = jnp.asarray(sizes, jnp.int32)
        want = _grouped_ref(x, w, sizes)
        scale = float(jnp.max(jnp.abs(want))) or 1.0
        for out in (grouped_matmul(x, w, s, out_dtype=out_dtype,
                                   interpret=True),
                    gmm_ragged(x, w, s, out_dtype=out_dtype)):
            assert out.shape == (m, n) and out.dtype == out_dtype
            err = jnp.max(jnp.abs(out.astype(jnp.float32) - want)) / scale
            assert float(err) < (1e-2 if out_dtype == jnp.bfloat16 else 1e-5)
        assert not jnp.any(out[sum(sizes):])

    def test_one_compile_for_any_sizes(self):
        x = jnp.ones((256, 128), jnp.bfloat16)
        w = jnp.ones((4, 128, 128), jnp.bfloat16)
        before = grouped_matmul._cache_size()
        for sizes in ([64, 64, 64, 64], [0, 0, 256, 0], [1, 2, 3, 4]):
            grouped_matmul(x, w, jnp.asarray(sizes, jnp.int32),
                           interpret=True)
        assert grouped_matmul._cache_size() == before + 1

    def test_cotangents_are_the_ragged_dots(self):
        x = jax.random.normal(jax.random.PRNGKey(2), (96, 128))
        w = jax.random.normal(jax.random.PRNGKey(3), (3, 128, 128))
        s = jnp.asarray([40, 0, 50], jnp.int32)

        def loss(f):
            return lambda x, w: jnp.sum(jnp.sin(f(x, w, s)))

        got = jax.grad(loss(lambda x, w, s: grouped_matmul(
            x, w, s, interpret=True)), argnums=(0, 1))(x, w)
        want = jax.grad(loss(gmm_ragged), argnums=(0, 1))(x, w)
        for g, r in zip(got, want):
            assert float(jnp.max(jnp.abs(g - r))) < 1e-3

    def test_row_tile_of_the_expert_gemms(self):
        """deepseek-moe's expert GEMMs take n whole in one k step on 512-row
        tiles; a decode step's few rows take one tile."""
        assert grouped_blocks(30720, 1408, 2048) == (512, 1408, 2048)
        assert grouped_blocks(30720, 2048, 1408) == (512, 2048, 1408)
        assert grouped_blocks(48, 1408, 2048)[0] == 48


class TestFlashAttention:
    def _ref(self, q, k, v, **kw):
        B, S, H, D = q.shape
        qh = q.transpose(0, 2, 1, 3).reshape(-1, S, D)
        kh = k.transpose(0, 2, 1, 3).reshape(-1, k.shape[1], D)
        vh = v.transpose(0, 2, 1, 3).reshape(-1, v.shape[1], D)
        o = attention_ref(qh, kh, vh, **kw)
        return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
    def test_causal_gqa(self, hq, hkv, dtype):
        B, S, D = 2, 256, 32
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, hq, D), dtype)
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, hkv, D), dtype)
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, hkv, D), dtype)
        out = mha(q, k, v, causal=True, block_q=128, block_kv=128, interpret=True)
        ref = self._ref(q, k, v, causal=True)
        err = jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32)))
        assert float(err) < (0.05 if dtype == jnp.bfloat16 else 1e-4)

    @pytest.mark.parametrize("window", [64, 200])
    def test_sliding_window(self, window):
        B, S, H, D = 1, 384, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(3), (B, S, H, D), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(4), (B, S, H, D), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(5), (B, S, H, D), jnp.float32)
        out = mha(q, k, v, causal=True, window=window,
                  block_q=128, block_kv=128, interpret=True)
        ref = self._ref(q, k, v, causal=True, window=window)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4

    def test_unaligned_query_length(self):
        B, S, H, D = 1, 300, 2, 32
        q = jax.random.normal(jax.random.PRNGKey(6), (B, S, H, D), jnp.float32)
        k = jax.random.normal(jax.random.PRNGKey(7), (B, 512, H, D), jnp.float32)
        v = jax.random.normal(jax.random.PRNGKey(8), (B, 512, H, D), jnp.float32)
        out = mha(q, k, v, causal=True, block_q=128, block_kv=128, interpret=True)
        ref = self._ref(q, k, v, causal=True)
        assert float(jnp.max(jnp.abs(out - ref))) < 1e-4
