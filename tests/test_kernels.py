"""Kernel validation: Pallas (interpreted on CPU) and jnp twins vs pure
oracles, swept over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.jax_slow

from repro.kernels.flash_attention.kernel import flash_fwd_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import mha_reference
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import ssd_naive
from repro.models.ssm import ssd_scan

FLASH_CASES = [
    # B, Sq, Skv, Hq, Hkv, D, causal, window
    (2, 128, 128, 4, 2, 32, True, 0),
    (1, 100, 100, 4, 4, 16, True, 0),       # ragged seq
    (2, 128, 128, 8, 2, 32, True, 24),      # sliding window
    (2, 64, 128, 4, 2, 16, False, 0),       # cross attention
    (1, 256, 256, 2, 1, 64, True, 0),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_jnp_matches_reference(case, dtype):
    B, Sq, Skv, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), dtype)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), dtype)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), dtype)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal, window, 32, 32, "jnp")
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    err = float(jnp.max(jnp.abs(ref.astype(jnp.float32)
                                - out.astype(jnp.float32))))
    assert err < tol, (case, dtype, err)


@pytest.mark.parametrize("case", FLASH_CASES)
def test_flash_pallas_matches_reference(case):
    B, Sq, Skv, Hq, Hkv, D, causal, window = case
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Skv, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Skv, Hkv, D), jnp.float32)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    out, lse = flash_fwd_pallas(q, k, v, causal=causal, window=window,
                                block_q=64, block_k=64)
    assert float(jnp.max(jnp.abs(ref - out))) < 2e-5
    # lse sanity: exp(lse) == softmax denominator > 0
    assert np.isfinite(np.asarray(lse)).all()


def test_flash_grads_match_reference():
    B, S, Hq, Hkv, D = 2, 96, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, S, Hq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, D), jnp.float32)

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

    def f_fl(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, True, 0, 32, 32,
                                               "jnp")))

    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    gf = jax.grad(f_fl, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gr, gf):
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5


SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 64, 4, 16, 1, 16, 16),
    (1, 96, 2, 32, 1, 8, 32),
    (2, 128, 4, 16, 2, 16, 64),
    (1, 50, 2, 16, 1, 16, 16),   # ragged
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_and_pallas_match_naive(case):
    B, S, H, P, G, N, chunk = case
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y0, s0 = ssd_naive(x, dt, A, Bm, Cm)
    pairs = [ssd_scan(x, dt, A, Bm, Cm, chunk=chunk),
             ssd_pallas(x, dt, A, Bm, Cm, chunk=chunk)]
    for y, s in pairs:
        assert float(jnp.max(jnp.abs(y0 - y))) < 1e-3
        assert float(jnp.max(jnp.abs(s0 - s))) < 1e-3


def test_ssd_decode_step_matches_scan():
    """Single-token recurrence == chunked scan, step by step."""
    B, S, H, P, G, N = 1, 12, 2, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_ref, final_ref = ssd_naive(x, dt, A, Bm, Cm)
    # sequential recurrence
    st = jnp.zeros((B, H, P, N))
    ys = []
    Bh = jnp.repeat(Bm, H // G, 2)
    Ch = jnp.repeat(Cm, H // G, 2)
    for t in range(S):
        dA = jnp.exp(dt[:, t] * A)                      # (B,H)
        upd = jnp.einsum("bh,bhp,bhn->bhpn", dt[:, t], x[:, t], Bh[:, t])
        st = st * dA[..., None, None] + upd
        ys.append(jnp.einsum("bhpn,bhn->bhp", st, Ch[:, t]))
    y_seq = jnp.stack(ys, 1)
    assert float(jnp.max(jnp.abs(y_seq - y_ref))) < 1e-4
    assert float(jnp.max(jnp.abs(st - final_ref))) < 1e-4


def test_ssd_init_state_threading():
    """Chunked scan with init state == one long scan split in two."""
    B, S, H, P, G, N = 1, 64, 2, 8, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, G, N)) * 0.5
    y_all, s_all = ssd_scan(x, dt, A, Bm, Cm, chunk=16)
    half = S // 2
    y1, s1 = ssd_scan(x[:, :half], dt[:, :half], A, Bm[:, :half],
                      Cm[:, :half], chunk=16)
    y2, s2 = ssd_scan(x[:, half:], dt[:, half:], A, Bm[:, half:],
                      Cm[:, half:], chunk=16, init_state=s1)
    assert float(jnp.max(jnp.abs(jnp.concatenate([y1, y2], 1) - y_all))) < 1e-4
    assert float(jnp.max(jnp.abs(s2 - s_all))) < 1e-4
