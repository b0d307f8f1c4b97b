"""Per-kernel allclose sweeps vs the ref.py pure-jnp oracles (interpret)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import api
from repro.core.csr import random_rhs, serial_solve
from repro.core.matrices import generate


# ------------------------------------------------------------------ sptrsv
@pytest.mark.parametrize("name,cpb", [
    ("chain_1k", 128), ("band_cz", 64), ("ckt_rajat04", 256), ("chem_bp", 32),
])
def test_sptrsv_kernel_vs_oracle(name, cpb):
    from repro.kernels.sptrsv import ops

    mat = generate(name)
    prog = api.compile(mat)
    b = random_rhs(mat, 3)
    x = ops.solve(prog, b, cycles_per_block=cpb, interpret=True)
    np.testing.assert_allclose(
        x, serial_solve(mat, b).astype(np.float32), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("name,placement", [
    ("hub_wall", "resident"), ("hub_wall", "blocked"),
    ("ckt_add20", "resident"), ("ckt_fpga", "blocked"),
])
def test_sptrsv_kernel_same_cycle_finals(name, placement):
    """Cycles whose FINAL lanes write several x rows at once: the kernel
    runs lanes one after another, which must equal the synchronous cycle."""
    from repro.core.program import OP_FINAL
    from repro.kernels.sptrsv import ops

    mat = generate(name)
    prog = api.compile(mat)
    finals = (prog.opcode == OP_FINAL).sum(axis=1)
    assert finals.max() >= 2, finals.max()
    bmat = np.random.default_rng(5).standard_normal((mat.n, 3))
    x = ops.solve(prog, bmat, cycles_per_block=64, interpret=True,
                  placement=placement)
    # same float32 operations in the same order as the lax.scan executor
    np.testing.assert_array_equal(x, api.solve_batch(prog, bmat))
    ref = np.stack([serial_solve(mat, bmat[:, j]) for j in range(3)], axis=1)
    np.testing.assert_allclose(x, ref, rtol=1e-5, atol=1e-5)


def test_sptrsv_kernel_vs_program_oracle():
    from repro.kernels.sptrsv import ops, ref

    mat = generate("band_cz")
    prog = api.compile(mat)
    b = random_rhs(mat, 4)
    np.testing.assert_allclose(
        ops.solve(prog, b, interpret=True),
        ref.solve_program(prog, b),
        rtol=1e-5, atol=1e-5,
    )


# ------------------------------------------------------------------ ssd_scan
@pytest.mark.parametrize("B,L,H,K,V", [
    (1, 64, 1, 8, 8),
    (2, 128, 2, 16, 32),
    (2, 200, 3, 32, 48),   # L not a chunk multiple -> padding path
    (1, 320, 2, 64, 64),
])
@pytest.mark.parametrize("inclusive", [True, False])
def test_ssd_scan_shapes(B, L, H, K, V, inclusive):
    from repro.kernels.ssd_scan import ops
    from repro.kernels.ssd_scan.ref import scan_ref

    rng = np.random.default_rng(hash((B, L, H, K, V, inclusive)) % 2**31)
    q = jnp.asarray(rng.standard_normal((B, L, H, K)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, H, K)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, L, H, V)), jnp.float32)
    w = jnp.asarray(-rng.uniform(0, 0.2, (B, L, H, K)), jnp.float32)
    s0 = jnp.asarray(rng.standard_normal((B, H, K, V)), jnp.float32) * 0.1
    u = None if inclusive else jnp.asarray(
        rng.standard_normal((H, K)), jnp.float32) * 0.1

    for use_pallas in (False, True):
        y, sf = ops.linear_recurrence(
            q, k, v, w, s0, u, chunk=64, inclusive=inclusive,
            use_pallas=use_pallas, interpret=True,
        )
        merge = lambda x, d: x.transpose(0, 2, 1, 3).reshape(B * H, L, d)
        yr, sfr = scan_ref(
            merge(q, K), merge(k, K), merge(v, V),
            jnp.clip(merge(w, K), ops.MIN_LOG_DECAY, 0),
            s0.reshape(B * H, K, V), inclusive=inclusive,
        )
        yr = yr.reshape(B, H, L, V).transpose(0, 2, 1, 3)
        if u is not None:
            gate = jnp.einsum("blhk,hk,blhk->blh", q, u, k)
            yr = yr + gate[..., None] * v
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(
            np.asarray(sf).reshape(B * H, K, V), np.asarray(sfr),
            rtol=2e-4, atol=2e-4,
        )


def test_ssd_scan_bf16():
    from repro.kernels.ssd_scan import ops

    rng = np.random.default_rng(0)
    B, L, H, K, V = 1, 128, 2, 16, 16
    mk = lambda s: jnp.asarray(rng.standard_normal(s), jnp.bfloat16)
    q, k, v = mk((B, L, H, K)), mk((B, L, H, K)), mk((B, L, H, V))
    w = -jnp.abs(mk((B, L, H, K))) * 0.1
    y16, _ = ops.linear_recurrence(q, k, v, w, use_pallas=False)
    y32, _ = ops.linear_recurrence(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), w.astype(jnp.float32), use_pallas=False,
    )
    assert y16.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(y16, np.float32), np.asarray(y32), rtol=0.1, atol=0.15
    )


def test_ssd_chunk_invariance():
    """Medium-granularity chunking must not change the math (chunk size is
    a pure performance knob — the psum feedback makes it exact)."""
    from repro.kernels.ssd_scan import ops

    rng = np.random.default_rng(5)
    B, L, H, K, V = 2, 256, 2, 16, 16
    q = jnp.asarray(rng.standard_normal((B, L, H, K)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, L, H, K)), jnp.float32) * 0.3
    v = jnp.asarray(rng.standard_normal((B, L, H, V)), jnp.float32)
    w = jnp.asarray(-rng.uniform(0, 0.2, (B, L, H, K)), jnp.float32)
    outs = [
        np.asarray(ops.linear_recurrence(q, k, v, w, chunk=c)[0])
        for c in (16, 64, 256)
    ]
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------ flash attention
@pytest.mark.parametrize("B,Lq,Hq,Hkv,D,bq,bk", [
    (1, 128, 2, 2, 32, 64, 64),
    (2, 200, 8, 2, 64, 64, 128),     # ragged lengths + GQA
    (1, 96, 4, 1, 128, 32, 32),      # MQA
])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention(B, Lq, Hq, Hkv, D, bq, bk, causal):
    from repro.kernels.flash_attention.ops import gqa_attention

    rng = np.random.default_rng(hash((B, Lq, Hq, causal)) % 2**31)
    q = jnp.asarray(rng.standard_normal((B, Lq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, Lq, Hkv, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, Lq, Hkv, D)), jnp.float32)
    o_ref = gqa_attention(q, k, v, causal=causal, use_pallas=False)
    o_pal = gqa_attention(q, k, v, causal=causal, use_pallas=True,
                          interpret=True, block_q=bq, block_k=bk)
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import gqa_attention

    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.standard_normal((1, 128, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 128, 2, 64)), jnp.bfloat16)
    o_ref = gqa_attention(q, k, v, use_pallas=False)
    o_pal = gqa_attention(q, k, v, use_pallas=True, interpret=True,
                          block_q=64, block_k=64)
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        rtol=0.05, atol=0.05,
    )


def test_attention_blocked_matches_exact():
    from repro.kernels.flash_attention.ref import attention_blocked, attention_ref

    rng = np.random.default_rng(7)
    for (bh, l, d, bk, causal) in [(4, 256, 32, 64, True), (2, 300, 64, 128, False),
                                   (1, 512, 16, 512, True)]:
        q = jnp.asarray(rng.standard_normal((bh, l, d)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((bh, l, d)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((bh, l, d)), jnp.float32)
        a = attention_ref(q, k, v, scale=d ** -0.5, causal=causal)
        b = attention_blocked(q, k, v, scale=d ** -0.5, causal=causal, block_k=bk)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)


def test_ssd_decode_fast_path_matches_chunked():
    from repro.kernels.ssd_scan import ops

    rng = np.random.default_rng(9)
    B, H, K, V = 2, 3, 16, 16
    s0 = jnp.asarray(rng.standard_normal((B, H, K, V)), jnp.float32) * 0.2
    # one-token step (fast path) vs the same step through the chunked path
    q = jnp.asarray(rng.standard_normal((B, 1, H, K)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, 1, H, K)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, 1, H, V)), jnp.float32)
    w = jnp.asarray(-rng.uniform(0, 0.2, (B, 1, H, K)), jnp.float32)
    for inclusive in (True, False):
        u = None if inclusive else jnp.asarray(
            rng.standard_normal((H, K)), jnp.float32) * 0.1
        y1, s1 = ops.linear_recurrence(q, k, v, w, s0, u, inclusive=inclusive)
        # chunked path forced by replicating the token to seq 8
        q8 = jnp.tile(q, (1, 8, 1, 1)); k8 = jnp.tile(k, (1, 8, 1, 1))
        v8 = jnp.tile(v, (1, 8, 1, 1)); w8 = jnp.tile(w, (1, 8, 1, 1))
        y8, _ = ops.linear_recurrence(q8, k8, v8, w8, s0, u,
                                      inclusive=inclusive, chunk=64)
        np.testing.assert_allclose(np.asarray(y1[:, 0]), np.asarray(y8[:, 0]),
                                   rtol=2e-4, atol=2e-4)
