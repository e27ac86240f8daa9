"""Differential-oracle suite: every Pallas kernel vs its ``ref.py`` in
interpret mode, concentrating on the shapes the per-kernel sweeps in
``test_kernels.py`` leave out — *unaligned/padded* dims (m not a multiple
of the 8-row sublane, d not a multiple of the 128 lane width) where the
wrappers' zero-padding must be exact — plus f32 tolerance sweeps across
input scales (padding or accumulation bugs show up as scale-dependent
error, not just large error).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attn.ops import flash_attention
from repro.kernels.flash_attn.ref import flash_ref
from repro.kernels.gram.ops import gram
from repro.kernels.gram.ref import gram_ref
from repro.kernels.power_iter.ops import power_iter
from repro.kernels.power_iter.ref import power_iter_ref
from repro.kernels.rank1_downdate.ops import rank1_downdate
from repro.kernels.rank1_downdate.ref import rank1_downdate_ref
from repro.kernels.window_gram.ops import window_gram
from repro.kernels.window_gram.ref import window_gram_ref

# deliberately hostile shapes: m ∉ 8ℤ, d ∉ 128ℤ, both prime-ish and tiny
UNALIGNED_MD = [(1, 1), (3, 5), (7, 130), (9, 127), (13, 257), (31, 333)]
SCALES = [1e-3, 1.0, 1e3]                     # f32 tolerance sweep


def _f32_tol(scale):
    # relative tolerance is scale-free; atol scales with the data's energy
    return dict(rtol=2e-4, atol=2e-4 * scale * scale)


@pytest.mark.parametrize("m,d", UNALIGNED_MD)
@pytest.mark.parametrize("scale", SCALES)
def test_gram_oracle_unaligned(m, d, scale):
    rng = np.random.default_rng(m * d + 1)
    x = jnp.asarray(scale * rng.normal(size=(m, d)), jnp.float32)
    np.testing.assert_allclose(np.asarray(gram(x, interpret=True)),
                               np.asarray(gram_ref(x)), **_f32_tol(scale))


@pytest.mark.parametrize("m", [1, 3, 7, 9, 13, 31])
def test_power_iter_oracle_unaligned(m):
    rng = np.random.default_rng(m)
    A = rng.normal(size=(m, 2 * m + 1)).astype(np.float32)
    K = jnp.asarray(A @ A.T)
    lam, u = power_iter(K, iters=64, interpret=True)
    lam_r, u_r = power_iter_ref(K, iters=64)
    np.testing.assert_allclose(float(lam), float(lam_r), rtol=1e-4)
    np.testing.assert_allclose(np.abs(np.asarray(u)),
                               np.abs(np.asarray(u_r)), atol=1e-3)


@pytest.mark.parametrize("m,d", UNALIGNED_MD)
@pytest.mark.parametrize("scale", SCALES)
def test_rank1_downdate_oracle_unaligned(m, d, scale):
    rng = np.random.default_rng(m + d)
    D = jnp.asarray(scale * rng.normal(size=(m, d)), jnp.float32)
    v = rng.normal(size=(d,))
    v = jnp.asarray(v / np.linalg.norm(v), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(rank1_downdate(D, v, interpret=True)),
        np.asarray(rank1_downdate_ref(D, v)), **_f32_tol(scale))


@pytest.mark.parametrize("n,d", [(1, 1), (7, 3), (9, 130), (127, 64),
                                 (129, 127), (250, 31)])
@pytest.mark.parametrize("scale", SCALES)
def test_window_gram_oracle_unaligned(n, d, scale):
    rng = np.random.default_rng(n + d)
    A = jnp.asarray(scale * rng.normal(size=(n, d)), jnp.float32)
    got = np.asarray(window_gram(A, interpret=True))
    want = np.asarray(window_gram_ref(A))
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * scale * scale * n)


@pytest.mark.parametrize("BH,BHkv,S,dh,causal", [
    (2, 1, 128, 64, True),                    # GQA group of 2
    (4, 4, 128, 32, False),                   # MHA, small head dim
    (3, 1, 256, 64, True),                    # odd head count
])
def test_flash_attn_oracle(BH, BHkv, S, dh, causal):
    ks = jax.random.split(jax.random.PRNGKey(BH * S), 3)
    q = jax.random.normal(ks[0], (BH, S, dh), jnp.float32)
    k = jax.random.normal(ks[1], (BHkv, S, dh), jnp.float32)
    v = jax.random.normal(ks[2], (BHkv, S, dh), jnp.float32)
    o = flash_attention(q, k, v, causal, 64, 64)
    o_ref, _ = flash_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_gram_psd_and_symmetry_invariants():
    """Structural invariants the oracle itself must satisfy — catches a
    broken ref.py as well as a broken kernel (true differential testing)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(13, 257)), jnp.float32)
    for K in (gram(x, interpret=True), gram_ref(x)):
        Kn = np.asarray(K, np.float64)
        np.testing.assert_allclose(Kn, Kn.T, atol=1e-5)
        assert np.linalg.eigvalsh(Kn).min() >= -1e-3


# ---------------------------------------------------------------------------
# Batched semantics: every kernel wrapper under vmap and shard_map
# (the wrappers pad-and-dispatch per call; the pallas vmap batching rule
# must keep that exact under a leading batch axis and inside an SPMD
# shard — the lowering the fused fleet tick runs under)
# ---------------------------------------------------------------------------

B = 4                                         # divisible by 1/2/4 devices


def _shard(fn):
    """shard_map a vmapped kernel call over all local devices
    (``check_vma=False``: pallas_call has no replication rule)."""
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("s",))
    n_in = 3 if fn.__code__.co_argcount == 3 else \
        (2 if fn.__code__.co_argcount == 2 else 1)
    return jax.shard_map(jax.vmap(fn), mesh=mesh,
                         in_specs=(P("s"),) * n_in, out_specs=P("s"),
                         check_vma=False)


@pytest.mark.parametrize("wrap", ["vmap", "shard_map"])
def test_gram_batched_oracle(wrap):
    rng = np.random.default_rng(7)
    xb = jnp.asarray(rng.normal(size=(B, 7, 130)), jnp.float32)
    fn = lambda x: gram(x, interpret=True)            # noqa: E731
    got = (jax.vmap(fn) if wrap == "vmap" else _shard(fn))(xb)
    want = np.stack([np.asarray(gram_ref(x)) for x in xb])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("wrap", ["vmap", "shard_map"])
def test_power_iter_batched_oracle(wrap):
    rng = np.random.default_rng(8)
    A = rng.normal(size=(B, 9, 19)).astype(np.float32)
    Kb = jnp.asarray(np.einsum("bij,bkj->bik", A, A))
    fn = lambda K: power_iter(K, iters=64, interpret=True)  # noqa: E731
    lam, u = (jax.vmap(fn) if wrap == "vmap" else _shard(fn))(Kb)
    for b in range(B):
        lam_r, u_r = power_iter_ref(Kb[b], iters=64)
        np.testing.assert_allclose(float(lam[b]), float(lam_r), rtol=1e-4)
        np.testing.assert_allclose(np.abs(np.asarray(u[b])),
                                   np.abs(np.asarray(u_r)), atol=1e-3)


@pytest.mark.parametrize("wrap", ["vmap", "shard_map"])
def test_rank1_downdate_batched_oracle(wrap):
    rng = np.random.default_rng(9)
    Db = jnp.asarray(rng.normal(size=(B, 13, 257)), jnp.float32)
    vb = rng.normal(size=(B, 257))
    vb = jnp.asarray(vb / np.linalg.norm(vb, axis=1, keepdims=True),
                     jnp.float32)
    fn = lambda D, v: rank1_downdate(D, v, interpret=True)  # noqa: E731
    got = (jax.vmap(fn) if wrap == "vmap" else _shard(fn))(Db, vb)
    want = np.stack([np.asarray(rank1_downdate_ref(Db[b], vb[b]))
                     for b in range(B)])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("wrap", ["vmap", "shard_map"])
def test_window_gram_batched_oracle(wrap):
    rng = np.random.default_rng(10)
    Ab = jnp.asarray(rng.normal(size=(B, 37, 31)), jnp.float32)
    fn = lambda A: window_gram(A, interpret=True)     # noqa: E731
    got = (jax.vmap(fn) if wrap == "vmap" else _shard(fn))(Ab)
    want = np.stack([np.asarray(window_gram_ref(A)) for A in Ab])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                               atol=2e-4 * 37)


@pytest.mark.parametrize("wrap", ["vmap", "shard_map"])
def test_flash_attn_batched_oracle(wrap, monkeypatch):
    # flash_attention has no interpret arg — force the pallas interpret
    # lowering via the env knob so the kernel (not ref) is under test
    monkeypatch.setenv("REPRO_KERNEL_LOWERING", "interpret")
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (B, 2, 128, 32), jnp.float32)
    k = jax.random.normal(ks[1], (B, 2, 128, 32), jnp.float32)
    v = jax.random.normal(ks[2], (B, 2, 128, 32), jnp.float32)
    fn = lambda q, k, v: flash_attention(q, k, v, True, 64, 64)  # noqa: E731
    got = (jax.vmap(fn) if wrap == "vmap" else _shard(fn))(q, k, v)
    want = jax.vmap(lambda q, k, v: flash_ref(q, k, v, causal=True)[0])(
        q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# The fused fleet tick vs the per-stream krylov path
# ---------------------------------------------------------------------------


def _run_krylov(d, eps, window, rows, *, use_pallas):
    from repro.core.dsfd import (dsfd_init, dsfd_query_rows, dsfd_update,
                                 make_config)
    cfg = make_config(d, eps, window, mode="krylov", use_pallas=use_pallas)
    st = dsfd_init(cfg)
    upd = jax.jit(lambda s, r, t: dsfd_update(cfg, s, r, t))
    for t in range(rows.shape[0]):
        st = upd(st, jnp.asarray(rows[t]), t + 1)
    return np.asarray(dsfd_query_rows(cfg, st))


def test_fused_tick_matches_per_stream_krylov():
    """Differential oracle for the tentpole: ``use_pallas=True`` routes
    the krylov dump loop through the fused kernel
    (``repro.kernels.fused_tick``); its sketch must match the inline
    per-stream path within f32 tolerance (documented: the fused kernel
    floors ‖w‖ at 1e-15 = sqrt(1e-30) where the inline path floors at
    1e-30 — indistinguishable off degenerate all-zero buffers — and the
    interpret/pallas lowering reassociates the Gram/matvec reductions).

    The lowering deliberately follows the session (``resolve_lowering``):
    ref in the plain CPU suite, the Pallas kernel body when CI job 2
    re-runs this file with ``REPRO_KERNEL_LOWERING=interpret``.  Forcing
    interpret here would put the very large emulated-kernel-inside-
    ``lax.while_loop`` HLO into every full-suite run, which has been
    seen to segfault XLA:CPU's compiler mid-suite; the interpret-mode
    compile is exercised in the standalone kernel-suite context
    instead."""
    rng = np.random.default_rng(21)
    d, n = 24, 160
    A = rng.normal(size=(n, d)).astype(np.float32)
    A[:, :3] *= 4.0
    A /= np.linalg.norm(A, axis=1, keepdims=True)
    B_inline = _run_krylov(d, 1 / 4, 48, A, use_pallas=False)
    B_fused = _run_krylov(d, 1 / 4, 48, A, use_pallas=True)
    scale = max(np.abs(B_inline).max(), 1e-6)
    np.testing.assert_allclose(B_fused, B_inline, rtol=2e-4,
                               atol=2e-4 * scale)


def test_fused_tick_vmap_streams_matches_scalar_loop():
    """The point of the fused path: under ``vmap_streams`` a fleet tick's
    krylov work is ONE batched kernel launch.  Its per-stream results
    must match running each stream through its own scalar update.

    Lowering follows the session (see
    ``test_fused_tick_matches_per_stream_krylov`` for why interpret is
    not forced here): both sides resolve identically, so the
    differential is lowering-agnostic.  The scalar side deliberately
    reuses ``_run_krylov`` with the SAME (d, eps, window) as the oracle
    test above, so its per-row program is a compile-cache hit — XLA:CPU
    has been seen to flakily segfault on a second, fresh scalar-krylov
    compile mid-suite, and this test's job is the vmap contract, not
    the scalar compile path."""
    from repro.sketch.api import make_sketch, vmap_streams
    rng = np.random.default_rng(22)
    S, n, d, win = 3, 96, 24, 48
    sk = make_sketch("dsfd", d=d, eps=1 / 4, window=win, mode="krylov",
                     use_pallas=True)
    fleet = vmap_streams(sk, S)
    X = rng.normal(size=(S, n, d)).astype(np.float32)
    X /= np.linalg.norm(X, axis=2, keepdims=True)
    st = fleet.init()
    st = fleet.update_block(st, jnp.asarray(X),
                            jnp.arange(1, n + 1, dtype=jnp.int32))
    B_fleet = np.asarray(fleet.query_rows(st, n))
    for s in range(S):
        B_one = _run_krylov(d, 1 / 4, win, X[s], use_pallas=True)
        scale = max(np.abs(B_one).max(), 1e-6)
        np.testing.assert_allclose(B_fleet[s], B_one, rtol=2e-4,
                                   atol=2e-4 * scale)
