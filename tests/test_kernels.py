"""Per-kernel correctness: Pallas (interpret mode) vs the pure-jnp oracles
in ``repro.kernels.ref`` across shape/dtype sweeps, plus hypothesis
property tests on the kernels' invariants."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.ggpu import isa
from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.pe_simd import pe_execute
from repro.kernels.rglru_scan import rglru_scan


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (bh, bhkv, sq, skv, hd, causal, window, dtype)
    (4, 2, 256, 256, 64, True, 0, jnp.float32),
    (4, 4, 128, 128, 32, False, 0, jnp.float32),      # bidirectional
    (8, 2, 200, 200, 64, True, 64, jnp.float32),      # ragged + SWA
    (2, 1, 384, 384, 128, True, 128, jnp.float32),    # deep GQA + window
    (2, 2, 128, 128, 64, True, 0, jnp.bfloat16),
    (6, 3, 96, 160, 64, False, 0, jnp.float32),       # cross lengths
]


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_attention_vs_ref(case):
    bh, bhkv, sq, skv, hd, causal, window, dtype = case
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(k1, (bh, sq, hd), dtype)
    k = jax.random.normal(k2, (bhkv, skv, hd), dtype)
    v = jax.random.normal(k3, (bhkv, skv, hd), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    expect = ref.attention_ref(q, k, v, causal=causal, window=window,
                               scale=hd ** -0.5)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), atol=tol)


def test_flash_block_size_invariance():
    q = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    k = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 64))
    v = jax.random.normal(jax.random.PRNGKey(3), (2, 256, 64))
    a = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                        interpret=True)
    b = flash_attention(q, k, v, causal=True, block_q=128, block_k=256,
                        interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


# ---------------------------------------------------------------------------
# rglru scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,d", [(1, 64, 128), (3, 100, 96), (2, 17, 40)])
def test_rglru_vs_ref(b, s, d):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (b, s, d)))
    bb = jax.random.normal(k2, (b, s, d))
    h0 = jax.random.normal(k3, (b, d))
    h, hf = rglru_scan(a, bb, h0, block_d=64, chunk=16, interpret=True)
    hr, hfr = ref.rglru_scan_ref(a, bb, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hf), np.asarray(hfr), atol=1e-5)


@given(st.integers(2, 30), st.integers(1, 3))
@settings(max_examples=10, deadline=None)
def test_rglru_composition_property(s, b):
    """Scanning [0:k) then [k:S) with the carried state == scanning [0:S)."""
    d = 16
    key = jax.random.PRNGKey(s * 7 + b)
    k1, k2, k3 = jax.random.split(key, 3)
    a = jax.nn.sigmoid(jax.random.normal(k1, (b, s, d)))
    bb = jax.random.normal(k2, (b, s, d))
    h0 = jax.random.normal(k3, (b, d))
    cut = max(1, s // 2)
    h_full, hf_full = ref.rglru_scan_ref(a, bb, h0)
    _, hf1 = rglru_scan(a[:, :cut], bb[:, :cut], h0, chunk=8,
                        interpret=True)
    h2, hf2 = rglru_scan(a[:, cut:], bb[:, cut:], hf1, chunk=8,
                         interpret=True)
    np.testing.assert_allclose(np.asarray(hf2), np.asarray(hf_full),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(h2), np.asarray(h_full[:, cut:]),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# pe_simd
# ---------------------------------------------------------------------------

def test_pe_simd_exact_all_ops():
    """Every ALU opcode, bit-exact vs the oracle."""
    ops_list = [isa.ADD, isa.SUB, isa.MUL, isa.MULH, isa.DIV, isa.REM, isa.AND, isa.OR,
                isa.XOR, isa.SLL, isa.SRL, isa.SRA, isa.SLT, isa.ADDI,
                isa.ANDI, isa.ORI, isa.XORI, isa.SLLI, isa.SRLI, isa.SRAI,
                isa.SLTI, isa.LUI]
    w, l = len(ops_list), 64
    op = jnp.asarray(ops_list, jnp.int32)[:, None]
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-10_000, 10_000, (w, l)), jnp.int32)
    b = jnp.asarray(rng.integers(-64, 64, (w, l)), jnp.int32)
    imm = jnp.asarray(rng.integers(0, 31, (w, 1)), jnp.int32)
    out = pe_execute(op, imm, a, b, interpret=True)
    expect = ref.pe_alu_ref(op, a, b, imm)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@given(st.integers(1, 40), st.integers(1, 128), st.integers(0, 99))
@settings(max_examples=15, deadline=None)
def test_pe_simd_property_random(w, l, seed):
    rng = np.random.default_rng(seed)
    op = jnp.asarray(rng.integers(1, 23, (w, 1)), jnp.int32)
    a = jnp.asarray(rng.integers(-2**20, 2**20, (w, l)), jnp.int32)
    b = jnp.asarray(rng.integers(-100, 100, (w, l)), jnp.int32)
    imm = jnp.asarray(rng.integers(-2048, 2048, (w, 1)), jnp.int32)
    out = pe_execute(op, imm, a, b, interpret=True)
    expect = ref.pe_alu_ref(op, a, b, imm)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


def test_pe_simd_matches_machine_alu():
    """The Pallas kernel and the simulator's exec_alu agree (the kernel is
    the TPU twin of the machine's hot loop)."""
    from repro.ggpu.engine.alu import exec_alu
    rng = np.random.default_rng(3)
    w, l = 16, 64
    op = jnp.asarray(rng.integers(1, 23, (w, 1)), jnp.int32)
    a = jnp.asarray(rng.integers(-1000, 1000, (w, l)), jnp.int32)
    b = jnp.asarray(rng.integers(-50, 50, (w, l)), jnp.int32)
    imm = jnp.asarray(rng.integers(-100, 100, (w, 1)), jnp.int32)
    kern = pe_execute(op, imm, a, b, interpret=True)
    sim = exec_alu(op, a, b, imm, None)
    # exclude MULH (int64 emulation differs on x64-disabled CPU)
    np.testing.assert_array_equal(np.asarray(kern), np.asarray(sim))


def test_mulh_vs_bigint():
    """The int32-only MULH decomposition is exact vs python big ints."""
    from repro.ggpu.engine.alu import _mulh32
    rng = np.random.default_rng(7)
    a = rng.integers(-2**31, 2**31, 10000).astype(np.int32)
    b = rng.integers(-2**31, 2**31, 10000).astype(np.int32)
    got = np.asarray(_mulh32(jnp.asarray(a), jnp.asarray(b)))
    exp = ((a.astype(object) * b.astype(object)) >> 32).astype(np.int64)
    np.testing.assert_array_equal(got, exp.astype(np.int32))
